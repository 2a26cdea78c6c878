#!/usr/bin/env python3
"""Bring-up smoke: drive PrIM workloads through ``PIMSystem`` on one TPU.

Every run goes through the user's entry point, ``Workload.run(PIMSystem(cfg),
...)``, which simulates on the accelerator through
``repro.core.compile_cache`` and checks its output against the workload's
numpy oracle (a mismatch raises and ends this script with a non-zero exit).
Phases, each run twice (cold, then warm):

* goldens: VA-scalar, VA-simt and BFS-scalar on the pinned 4-DPU system;
  cycles, issued instructions and timeline totals must equal
  ``repro.goldens`` bit for bit;
* one rank: Table I DPUs, 64 of them with 16 tasklets: VA, BFS
  (multi-kernel, host-priced collectives), SSORT (alltoall) and GEMVS on
  the HBM-PIM command backend;
* full server: VA on PrIM's 2,560-DPU server (40 ranks of 64), on 2,560
  engine lanes (its DPU bucket).

Each run prints one ``bring-up`` line: wall and compile seconds, hits in
JAX's persistent compile cache, simulated cycles and issued instructions,
and the compile-cache counters it added (a warm run must add no miss).
These are bring-up numbers, not a benchmark.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before running anything.

    python chip_smoke.py
"""
from __future__ import annotations

import hashlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro.workloads as wl  # noqa: E402
from repro.core import compile_cache  # noqa: E402
from repro.core.config import DPUConfig  # noqa: E402
from repro.core.host import PIMSystem  # noqa: E402
from repro.goldens import GOLDENS, run_golden  # noqa: E402

TASKLETS = 16                           # Table I
ONE_RANK_DPUS = 64
# (workload, scale, config overrides); MRAM is sized to the image
ONE_RANK_RUNS = [
    ("VA", 1.0, dict(mram_bytes=256 << 10)),
    ("BFS", 0.1, dict(mram_bytes=1 << 20)),
    ("SSORT", 0.02, dict(mram_bytes=1 << 20)),
    ("GEMVS", 1.0, dict(mram_bytes=1 << 20, backend="hbmpim_cmd")),
]
SERVER_DPUS, SERVER_RANKS = 2560, 40    # PrIM's server (arXiv:2105.03814)
# 0.25 took 191 s per run on a TPU v5e (3.4 ms per loop iteration on
# 4,096 lanes, 1,536 of them padded); 0.1 kept each run near a minute
# and a half
SERVER_SCALE = 0.1
SERVER_MRAM_BYTES = 64 << 10
# VA at SERVER_SCALE on one DPU (CPU run): its timing does not depend on
# the DPU count, so every one of the server's DPUs must reproduce these
VA_SERVER_CYCLES = 22819
VA_SERVER_ISSUED_PER_DPU = 15248
VA_SERVER_TLP_SHA256 = (
    "a1ae9ff0de4d9fff5475e7b7d255f8ea13545451b6d3de8f2f17fd1305242540")

# JAX compile events seen since the current run started (timed() resets)
_jax = {"compile_s": 0.0, "persistent_cache_hits": 0}


def _on_duration(event: str, seconds: float, **_):
    if event.startswith("/jax/core/compile/"):
        _jax["compile_s"] += seconds


def _on_event(event: str, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _jax["persistent_cache_hits"] += 1


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (device 0 is "
                 f"{dev.platform!r}); refusing to run on another platform")
    return dev


def timed(label: str, fn) -> dict:
    """Run ``fn() -> (cycles, issued)`` once and print its bring-up line."""
    before = compile_cache.stats()
    _jax.update(compile_s=0.0, persistent_cache_hits=0)
    t0 = time.perf_counter()
    cycles, issued = fn()
    wall = time.perf_counter() - t0
    after = compile_cache.stats()
    delta = {k: after[k] - before[k] for k in ("misses", "hits", "launches")}
    print("bring-up " + json.dumps({
        "run": label, "wall_s": wall, **_jax,
        "cycles": int(cycles), "issued": int(issued), "cache": delta}),
        flush=True)
    return delta


def cold_warm(label: str, fn):
    timed(f"{label}:cold", fn)
    warm = timed(f"{label}:warm", fn)
    if warm["misses"]:
        raise AssertionError(f"{label}: warm rerun compiled "
                             f"{warm['misses']} new executable(s)")


def golden(name: str):
    def go():
        got = run_golden(name)
        if got != GOLDENS[name]:
            raise AssertionError(f"{name}: (cycles, issued, timeline.total, "
                                 f"timeline.kernel) = {got}, pinned "
                                 f"{GOLDENS[name]}")
        return got[:2]
    return go


def workload(name: str, cfg: DPUConfig, scale: float, check=None):
    def go():
        system = PIMSystem(cfg)
        _, rep = wl.get(name).run(system, TASKLETS, scale=scale, seed=0)
        if check is not None:
            check(rep)
        return rep.cycles, rep.issued
    return go


def check_server(rep):
    want = (VA_SERVER_CYCLES, VA_SERVER_ISSUED_PER_DPU * SERVER_DPUS)
    if (rep.cycles, rep.issued) != want:
        raise AssertionError(f"VA server (cycles, issued) = "
                             f"{(rep.cycles, rep.issued)}, want {want}")
    # the TLP series (float32 window averages) against the CPU's: reported,
    # not asserted, since no simulated statistic is derived from it
    same = all(hashlib.sha256(np.ascontiguousarray(row).tobytes()).hexdigest()
               == VA_SERVER_TLP_SHA256 for row in rep.ts)
    print(f"bring-up tlp_series_matches_cpu={same}", flush=True)


def print_peak(dev, phase: str):
    stats = dev.memory_stats() or {}
    print(f"bring-up {phase}: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)


def main():
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    dev = require_tpu()
    cache_dir = compile_cache.use_persistent_cache()
    print(f"bring-up device={dev.device_kind} count={len(jax.devices())} "
          f"jax={jax.__version__} compile_cache_dir={cache_dir}", flush=True)

    for name in sorted(GOLDENS):
        cold_warm(f"goldens/{name}", golden(name))
    print_peak(dev, "goldens")

    for name, scale, kw in ONE_RANK_RUNS:
        cfg = DPUConfig(n_dpus=ONE_RANK_DPUS, n_tasklets=TASKLETS, **kw)
        cold_warm(f"one_rank/{name}", workload(name, cfg, scale))
    print_peak(dev, "one_rank")

    cfg = DPUConfig(n_dpus=SERVER_DPUS, n_ranks=SERVER_RANKS,
                    n_tasklets=TASKLETS, mram_bytes=SERVER_MRAM_BYTES)
    cold_warm("full_server/VA",
              workload("VA", cfg, SERVER_SCALE, check=check_server))
    print_peak(dev, "full_server")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
