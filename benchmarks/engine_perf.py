"""Measured PIM-engine performance on the device JAX runs on; every row
and the report name it (``platform``, ``device_kind``, ``device_count``).

Two views:

* **Launch latency** — cold first launch (XLA trace + compile through
  ``repro.core.compile_cache``) vs. warm same-shape relaunch (cache hit).
  The warm path is the one every iterated workload (BFS levels, NW
  sweeps, SSORT phases, ``launch(dpus=...)`` subsets) actually sees.
* **Steady state** — simulated-cycles-per-second and KIPS = simulated
  instructions / wall-second of a warm run (paper's PIMulator: 3 KIPS,
  single DPU).

``--json BENCH_5.json`` emits the machine-readable report; ``--check``
gates warm < cold (the CI regression tripwire), ``--min-speedup N``
tightens the gate (the PR acceptance bar is 10x).
"""
from __future__ import annotations

import json
import time

import jax
import numpy as np

import repro.workloads as wl
from repro.core import compile_cache, engine
from repro.core.config import DPUConfig


def device_info() -> dict:
    """The device every timing of this run was taken on."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _setup(name: str, scale: float, n_threads: int, mram_bytes=1 << 21,
           **cfg_kw):
    cfg = DPUConfig(n_tasklets=max(n_threads, 16), mram_bytes=mram_bytes,
                    **cfg_kw)
    W = wl.get(name)
    hd = W.host_data(cfg, scale, 0)
    binary = W.build(n_threads).binary(cfg.iram_instrs)
    wram = np.zeros((cfg.n_dpus, 16), np.int32)
    wram[:, :hd.args.shape[1]] = hd.args
    return cfg, binary, wram, hd.mram


def launch_latency(name: str = "VA", scale: float = 0.005, n_dpus: int = 4,
                   n_threads: int = 16, warm_reps: int = 3, **cfg_kw):
    """Cold (compile + run) vs. warm (cache hit + run) launch wall time.

    Uses a small kernel so launch overhead, not simulated cycles,
    dominates — the launch-heavy pattern of iterated workloads."""
    cfg, binary, wram, mram = _setup(name, scale, n_threads, n_dpus=n_dpus,
                                     mram_bytes=1 << 18, **cfg_kw)
    compile_cache.clear()
    t0 = time.perf_counter()
    out = engine.run(cfg, binary, wram, mram, n_threads)
    cold_s = time.perf_counter() - t0
    warm = []
    for _ in range(warm_reps):
        t0 = time.perf_counter()
        out = engine.run(cfg, binary, wram, mram, n_threads)
        warm.append(time.perf_counter() - t0)
    warm_s = float(np.median(warm))
    cycles = int(np.asarray(out["cycle"]).max())
    issued = int(np.asarray(out["c_issued"]).sum())
    cs = compile_cache.stats()
    assert cs["misses"] == 1, cs  # every relaunch hit the cache
    return {
        "workload": name, "dpus": n_dpus, "threads": n_threads,
        "cold_s": round(cold_s, 3), "warm_s": round(warm_s, 4),
        "speedup": round(cold_s / max(warm_s, 1e-9), 1),
        "cycles": cycles, "issued": issued,
        "warm_kips": round(issued / warm_s / 1e3, 1),
        "warm_cycles_per_s": int(cycles / warm_s),
    }


def subset_reuse(name: str = "VA", scale: float = 0.1, n_dpus: int = 8,
                 n_threads: int = 16):
    """Warm latency of ``launch(dpus=...)`` subset sizes sharing one
    DPU bucket (pre-cache: every size was a fresh compile)."""
    from repro.core.host import PIMSystem
    cfg = DPUConfig(n_tasklets=n_threads, mram_bytes=1 << 18, n_dpus=n_dpus)
    W = wl.get(name)
    hd = W.host_data(cfg, scale, 0)
    binary = W.build(n_threads).binary(cfg.iram_instrs)
    sys_ = PIMSystem(cfg)
    sys_.launch(name, binary, hd.args, hd.mram, n_threads=n_threads)  # warm
    m0 = compile_cache.stats()["misses"]
    times = {}
    for k in range(n_dpus // 2 + 1, n_dpus + 1):   # all in one pow2 bucket
        t0 = time.perf_counter()
        sys_.launch(name, binary, hd.args, hd.mram, n_threads=n_threads,
                    dpus=list(range(k)))
        times[k] = round(time.perf_counter() - t0, 4)
    return {"workload": name, "dpus": n_dpus,
            "subset_warm_s": times,
            "new_compiles": compile_cache.stats()["misses"] - m0}


def steady_state(name: str, scale: float, n_threads: int = 16, **cfg_kw):
    """Returns dict(compile_s, run_s, cycles, issued, kips, cps).

    ``compile_s`` is 0 when the first run was already a cross-kernel
    cache hit (the shared compile cache makes that common)."""
    cfg, binary, wram, mram = _setup(name, scale, n_threads, **cfg_kw)
    misses0 = compile_cache.stats()["misses"]
    t0 = time.perf_counter()
    out = engine.run(cfg, binary, wram, mram, n_threads)
    t_first = time.perf_counter() - t0
    cold = compile_cache.stats()["misses"] > misses0
    t0 = time.perf_counter()
    out = engine.run(cfg, binary, wram, mram, n_threads)
    t_run = time.perf_counter() - t0
    compile_s = max(0.0, t_first - t_run) if cold else 0.0
    cycles = int(np.asarray(out["cycle"]).max())
    issued = int(np.asarray(out["c_issued"]).sum())
    return {
        "workload": name, "dpus": cfg.n_dpus, "threads": n_threads,
        "compile_s": round(compile_s, 2), "run_s": round(t_run, 3),
        "cycles": cycles, "issued": issued,
        "kips": round(issued / t_run / 1e3, 1),
        "cycles_per_s": int(cycles / t_run),
    }


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.3)
    ap.add_argument("--launch-scale", type=float, default=0.005,
                    help="workload scale for the launch-latency probe "
                    "(small, so launch overhead dominates — the regime "
                    "of iterated kernels, cf. arXiv:2105.03814)")
    ap.add_argument("--json", default="", help="write BENCH_5.json report")
    ap.add_argument("--check", action="store_true",
                    help="fail unless warm relaunch beats cold launch")
    ap.add_argument("--min-speedup", type=float, default=1.0,
                    help="with --check: required cold/warm ratio")
    args = ap.parse_args()
    compile_cache.use_persistent_cache()
    device = device_info()

    print("== launch latency: cold (compile) vs warm (cache hit) ==")
    lat = {**launch_latency("VA", args.launch_scale), **device}
    print(lat)
    print("== subset launches sharing one DPU bucket ==")
    sub = {**subset_reuse("VA", args.launch_scale), **device}
    print(sub)
    print("== steady-state engine throughput ==")
    rows = []
    for d in (1, 4, 16, 64):
        r = {**steady_state("VA", args.scale, n_dpus=d), **device}
        rows.append(r)
        print(r)
    for skip in (False, True):
        r = {**steady_state("BS", args.scale, n_dpus=1, event_skip=skip),
             "event_skip": skip, **device}
        rows.append(r)
        print(r)

    report = {"device": device, "launch": lat, "subset_reuse": sub,
              "steady_state": rows, "cache": compile_cache.stats()}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.json}")
    if args.check:
        assert lat["warm_s"] < lat["cold_s"], (
            f"warm relaunch {lat['warm_s']}s not faster than cold "
            f"{lat['cold_s']}s")
        assert lat["speedup"] >= args.min_speedup, (
            f"cold/warm speedup {lat['speedup']}x < {args.min_speedup}x")
        assert sub["new_compiles"] == 0, sub
        print(f"CHECK OK: warm {lat['warm_s']}s < cold {lat['cold_s']}s "
              f"({lat['speedup']}x), subset launches compiled nothing new")
    return report


if __name__ == "__main__":
    main()
