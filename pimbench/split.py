#!/usr/bin/env python3
"""Split a cell's host time and device idle time by the program's own spans.

    python3 pimbench/split.py --workload rank64.bfs --seed 7

The program marks its layers with host spans (``repro.*``, see
``repro.obs.spans``) and counts engine loop iterations and uploaded
bytes (``compile_cache.stats()``).  This script prewarms a cell as the
harness does, profiles exactly one simulation (the first of the
harness's data-seed order for ``--seed``), reads the program's spans
from the trace with their args, and prints one JSON line:

* ``metrics``: the per-layer readings that the spans and counters give
  (:func:`readings`), next to ``harness``, the benchmark's own per-layer
  metrics of the same simulation, read by its own readers;
* ``idle_by_span``: each idle second of the device inside the traced
  simulation, put down to the innermost program span that covers it
  (``repro.sim`` where none deeper does);
* ``consistency``: the identities between the two sets of readings.

Refuses to run, printing no result, where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from pimbench import harness, spec, trace  # noqa: E402
from pimbench.window import data_seeds  # noqa: E402

PREFIX = "repro."
SIM = "repro.sim"
#: reading -> the span whose mean wall per launch it is
LAUNCH_PARTS = {"launch_prepare_ms": "repro.launch.prepare",
                "launch_upload_ms": "repro.launch.upload",
                "launch_readback_ms": "repro.launch.readback"}
HOST_RUNTIME = ("repro.host.", "repro.comm.", "repro.sched.")
ENGINE_MODULE = "jit_pim_engine"
#: the benchmark's per-layer metrics that the consistency checks compare
HARNESS_METRICS = ("launch_host_ms", "host_ms_per_sim", "engine_ns_per_cycle",
                   "device_idle_share", "state_mb_per_launch")


@dataclass
class Span:
    name: str
    start: int            # ns on the profiler's clock
    end: int
    args: dict


def spans_from_xplane(path: Path) -> List[Span]:
    """The program's ``repro.*`` host spans of an ``.xplane.pb`` file."""
    import jax
    prof = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append(Span(ev.name, s, s + int(ev.duration_ns),
                                    dict(ev.stats)))
    return sorted(out, key=lambda x: (x.start, -x.end))


def spans_from_rows(rows) -> List[Span]:
    """From the JSON form ``[[name, start, end, {args}], ...]``."""
    return sorted((Span(n, int(s), int(e), dict(a)) for n, s, e, a in rows),
                  key=lambda x: (x.start, -x.end))


def _busy_before(ms: np.ndarray, me: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Busy nanoseconds before each time of ``t`` in the disjoint sorted
    intervals ``(ms, me)``."""
    cum = np.concatenate([[0], np.cumsum(me - ms)])
    i = np.searchsorted(me, t, side="right")      # intervals ended by t
    part = np.where(i < len(ms),
                    np.clip(t - ms[np.minimum(i, len(ms) - 1)], 0, None), 0)
    return cum[i] + part


def idle_by_span(tr: trace.Trace, spans: List[Span]) -> Dict[str, float]:
    """Seconds of device idle time inside each ``repro.sim`` span, by the
    innermost program span covering it, mean over devices."""
    out: Dict[str, float] = {}
    for sim in (s for s in spans if s.name == SIM):
        inner = [s for s in spans
                 if sim.start <= s.start and s.end <= sim.end]
        edges = np.unique([t for s in inner for t in (s.start, s.end)])
        # innermost span of each elementary stretch: the covering span
        # that starts last (spans of one thread nest)
        owner = []
        for a, b in zip(edges[:-1], edges[1:]):
            cover = [s for s in inner if s.start <= a and b <= s.end]
            owner.append(max(cover, key=lambda s: (s.start, -s.end)).name)
        for dev in sorted(tr.ops):
            _, st, en = tr.ops[dev]
            ms, me = trace.union(st, en)
            busy = (np.diff(_busy_before(ms, me, edges)) if len(ms)
                    else np.zeros(len(edges) - 1, np.int64))
            for name, idle in zip(owner, np.diff(edges) - busy):
                out[name] = out.get(name, 0.0) + idle / 1e9 / len(tr.ops)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _union_ns(spans: List[Span]) -> int:
    if not spans:
        return 0
    s, e = trace.union(np.array([x.start for x in spans], np.int64),
                       np.array([x.end for x in spans], np.int64))
    return int((e - s).sum())


def _mean_ms(spans: List[Span], name: str):
    walls = [s.end - s.start for s in spans if s.name == name]
    return sum(walls) / len(walls) / 1e6 if walls else None


def readings(tr: trace.Trace, spans: List[Span], sim: harness.Sim,
             counters: List[Tuple[int, int]]) -> Dict[str, float]:
    """The per-layer readings of the program's spans and counters, for
    the traced simulation ``sim``.

    ``counters[j]`` is ``(loop_iters, h2d_bytes)`` of its launch ``j``.
    Per-span readings are means over its launches or totals for it.  A
    reading with nothing to read is left out."""
    out = {}
    for reading, name in LAUNCH_PARTS.items():
        ms = _mean_ms(spans, name)
        if ms is not None:
            out[reading] = ms
    iters = sum(i for i, _ in counters)
    uploaded = sum(b for _, b in counters)
    cycles = sum(int(x.cycles.max()) for x in sim.launches)
    if counters and uploaded:
        out["upload_mb_per_launch"] = uploaded / len(counters) / 1e6
    if iters and cycles:
        out["engine_iters_per_cycle"] = iters / cycles
    top = next((s for s in spans if s.name == SIM), None)
    if top is None:
        return out
    engine_ns = []
    for dev in sorted(tr.modules):
        names, st, en = tr.modules[dev]
        mine = np.array([n.startswith(ENGINE_MODULE) for n in names], bool)
        inside = mine & (st >= top.start) & (st < top.end)
        engine_ns.append(int((en - st)[inside].sum()))
    if iters and sum(engine_ns):
        out["engine_us_per_iter"] = (sum(engine_ns) / len(engine_ns)
                                     / iters / 1e3)
    children = [s for s in spans if s is not top
                and top.start <= s.start and s.end <= top.end]
    out["host_runtime_ms_per_sim"] = _union_ns(
        [s for s in children if s.name.startswith(HOST_RUNTIME)]) / 1e6
    out["workload_host_ms_per_sim"] = (
        top.end - top.start - _union_ns(children)) / 1e6
    return out


def consistency(m: Dict[str, float], h: Dict[str, float],
                idle: Dict[str, float]) -> Dict[str, float]:
    """Each identity between the readings, as a ratio (1 is exact), and
    the share of idle time that a span deeper than ``repro.sim`` holds."""
    out = {}
    if {"engine_us_per_iter", "engine_iters_per_cycle"} <= m.keys() \
            and h.get("engine_ns_per_cycle"):
        out["engine_identity"] = (m["engine_us_per_iter"]
                                  * m["engine_iters_per_cycle"] * 1e3
                                  / h["engine_ns_per_cycle"])
    parts = [m.get(reading) for reading in LAUNCH_PARTS]
    if None not in parts and h.get("launch_host_ms"):
        out["launch_parts_over_launch_host"] = sum(parts) / h["launch_host_ms"]
    if {"host_runtime_ms_per_sim", "workload_host_ms_per_sim"} <= m.keys() \
            and h.get("host_ms_per_sim"):
        out["host_parts_over_host_ms"] = (
            (m["host_runtime_ms_per_sim"] + m["workload_host_ms_per_sim"])
            / h["host_ms_per_sim"])
    if sum(idle.values()):
        out["idle_below_sim_share"] = 1 - idle.get(SIM, 0.0) / sum(
            idle.values())
    return out


def probe_counters(launches: list):
    """Wraps the program's ``compile_cache.run`` (outside the harness's
    own probe): appends each launch's ``(loop_iters, h2d_bytes)`` delta
    of ``compile_cache.stats()``, 0 where the program has no such
    counter.  Returns the function that undoes it."""
    from repro.core import compile_cache
    inner = compile_cache.run

    def run(*args, **kw):
        s0 = compile_cache.stats()
        out = inner(*args, **kw)
        s1 = compile_cache.stats()
        launches.append(tuple(s1.get(k, 0) - s0.get(k, 0)
                              for k in ("loop_iters", "h2d_bytes")))
        return out

    compile_cache.run = run
    return lambda: setattr(compile_cache, "run", inner)


def measure(cell: spec.Cell, seed: int) -> dict:
    """Prewarm ``cell``, profile one simulation of it and return the
    result line."""
    import jax
    prog = harness.Program(cell)
    prog.prewarm()
    sim = harness.Sim(data_seed=int(
        data_seeds(seed, int(cell.traffic["seed_pool"]))[0]))
    tdir = Path(tempfile.mkdtemp(prefix="pimbench-split-"))
    counters: list = []
    undo = probe_counters(counters)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        prog.simulate(sim, spans=True)
    finally:
        jax.profiler.stop_trace()
        undo()
    (path,) = sorted(tdir.rglob("*.xplane.pb"))[-1:]
    tr = trace.Trace.from_xplane(path)
    spans = spans_from_xplane(path)
    shutil.rmtree(tdir, ignore_errors=True)

    # set-up is no reading here, and none of HARNESS_METRICS reads it
    obs = harness.Observation(setup_s=0.0, window_s=sim.end - sim.start,
                              sims=[sim], trace=trace.summarize(tr))
    h = {m: spec.metric_reader(m).read(obs) for m in HARNESS_METRICS}
    m = readings(tr, spans, sim, counters)
    idle = idle_by_span(tr, spans)
    dev = jax.devices()[0]
    return {
        "cell": cell.name, "seed": seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "sim": {"data_seed": sim.data_seed, "wall_s": sim.end - sim.start,
                "launches": len(sim.launches), "error": sim.error,
                "spans": sum(s.name.startswith(PREFIX) for s in spans)},
        "metrics": m, "harness": h, "idle_by_span": idle,
        "idle_s": obs.trace.window_s - obs.trace.busy_s,
        "consistency": consistency(m, h, idle),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    harness.use_compile_cache(Path(os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", ROOT / ".jax_cache")))
    import jax
    try:
        harness.require_chips(jax.devices(), cell.chips)
    except harness.NoChip as e:
        print(f"pimbench split: {e}; refusing to run", file=sys.stderr)
        return 2
    print(json.dumps(measure(cell, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
