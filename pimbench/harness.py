"""One run of one cell: set-up, the closed-loop window, the check.

A run is a closed loop with one client: simulations of the cell's traffic
back to back, each ``Workload.run(PIMSystem(cfg), tasklets, scale, seed)``
as a user calls it.  The window ends with the simulation that is in
flight when ``seconds`` have passed; it finishes and counts, so the rate
is all the work over all the time.  The run seed orders a pool of data
seeds; the simulated statistics of every pool seed are pinned in
``expected/<cell>.json``.

Every engine launch (``repro.core.compile_cache.run``, looked up at call
time) passes through a probe that keeps each DPU's cycles and issued
instructions for the check.  With ``trace`` on, the benchmark's spans
wrap ``Workload.run`` and every launch, and the profiler records the
run's first simulation.
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

from pimbench import check, spec
from pimbench.trace import SPAN_LAUNCH, SPAN_SIM, Summary, Trace, summarize
from pimbench.window import closed_loop, data_seeds

#: executables compiled inside the window are counted from this JAX event
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(devices, chips: int):
    """Refuse any platform but TPU, and fewer TPU chips than asked for."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise NoChip(f"JAX found {found!r}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def use_compile_cache(path: Path):
    """JAX's persistent compilation cache at a fixed path, caching every
    executable so that a later run's set-up compiles nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclass
class Launch:
    start: float
    end: float
    state_bytes: int          # the state returned to the host
    cycles: np.ndarray        # per DPU
    issued: np.ndarray        # per DPU


@dataclass
class Sim:
    data_seed: int
    start: float = 0.0
    end: float = 0.0
    issued: int = 0
    stats: Optional[dict] = None
    image: Optional[np.ndarray] = None   # leading MRAM words after the run
    error: Optional[str] = None
    launches: List[Launch] = field(default_factory=list)


@dataclass
class Observation:
    """What the metric readers (``metrics/<name>.py``) read.  With a
    trace, ``sims[0]`` is the simulation that the profiler recorded."""

    setup_s: float
    window_s: float
    sims: List[Sim]
    trace: Optional[Summary] = None                       # trace runs only


def sim_stats(rep, system) -> dict:
    """The simulated statistics that a change for speed may not alter."""
    tl = system.timeline
    return {"cycles": int(rep.cycles), "issued": int(rep.issued),
            "launches": len(system.reports), "h2d_s": tl.h2d,
            "kernel_s": tl.kernel, "d2h_s": tl.d2h,
            "inter_dpu_s": tl.inter_dpu, "total_s": tl.total}


class Program:
    """The system under test for one cell, at the cell's configuration."""

    def __init__(self, cell: spec.Cell, dpu_override: Optional[dict] = None):
        import repro.workloads as wl
        from repro.core.config import DPUConfig
        from repro.core.host import PIMSystem
        self.PIMSystem = PIMSystem
        self.dpu = {**cell.dpu(), **(dpu_override or {})}
        self.cfg = DPUConfig(**self.dpu)
        self.traffic = cell.traffic
        self.workload = wl.get(self.traffic["workload"])
        self.width = cell.reference().words(self.dpu, self.traffic["sizes"])

    def prewarm(self):
        """Compile (or load from the persistent cache) the cell's one
        engine executable, simulating nothing."""
        t = self.cfg.n_tasklets
        binary = self.workload.build(t).binary(self.cfg.iram_instrs)
        self.PIMSystem(self.cfg).prewarm(binary, n_threads=t)

    def simulate(self, sim: Sim, spans: bool = False):
        """One user-level simulation; fills ``sim`` and never raises.
        With ``spans``, the simulation and its launches are wrapped in
        the benchmark's spans."""
        import jax
        system = self.PIMSystem(self.cfg)
        sim.start = time.perf_counter()
        try:
            with (jax.profiler.TraceAnnotation(SPAN_SIM) if spans
                  else contextlib.nullcontext()), \
                    probe_launches(sim.launches, spans):
                st, rep = self.workload.run(
                    system, self.cfg.n_tasklets,
                    scale=self.traffic["scale"], seed=sim.data_seed)
            sim.end = time.perf_counter()
            sim.issued = int(rep.issued)
            sim.stats = sim_stats(rep, system)
            sim.image = np.array(st["mram"][:, :self.width])
        except Exception as e:  # a failed simulation is counted, not fatal
            sim.end = time.perf_counter()
            sim.error = f"{type(e).__name__}: {e}"
            traceback.print_exc(file=sys.stderr)


@contextlib.contextmanager
def probe_launches(launches: List[Launch], span: bool):
    """Installed on the program's ``compile_cache.run`` for the length of
    one simulation: keeps each launch's per-DPU counters in ``launches``,
    and with ``span`` wraps the launch in the benchmark's span."""
    import jax
    from repro.core import compile_cache
    inner = compile_cache.run

    def run(*args, **kw):
        t0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation(SPAN_LAUNCH) if span
              else contextlib.nullcontext()):
            out = inner(*args, **kw)
        launches.append(Launch(
            t0, time.perf_counter(),
            sum(int(np.asarray(x).nbytes)
                for x in jax.tree_util.tree_leaves(out)),
            np.array(out["cycle"]), np.array(out["c_issued"])))
        return out

    compile_cache.run = run
    try:
        yield
    finally:
        compile_cache.run = inner


def _compile_counter():
    import jax
    box = {"n": 0}

    def listen(event: str, seconds: float, **_):
        if event == COMPILE_EVENT:
            box["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return box


def peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
            t0: float, dpu_override: Optional[dict] = None,
            expected: Optional[dict] = None,
            log: Callable[[str], None] = print) -> dict:
    """Run one cell and return the result line as a dict.

    ``t0`` is the process's start on ``time.perf_counter``'s clock;
    set-up runs from it to the start of the first timed simulation."""
    import jax
    from repro.core import compile_cache
    prog = Program(cell, dpu_override)
    prog.prewarm()
    compiles = _compile_counter()
    misses0 = compile_cache.stats()["misses"]
    order = data_seeds(seed, int(cell.traffic["seed_pool"]))
    tdir = Path(tempfile.mkdtemp(prefix="pimbench-trace-")) if trace else None

    def one(k: int) -> Sim:
        sim = Sim(data_seed=int(order[k % len(order)]))
        if k == 0 and trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # it would slow the host tenfold
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        prog.simulate(sim, trace)
        if k == 0 and trace:
            jax.profiler.stop_trace()
        return sim

    start, end, sims = closed_loop(one, seconds)
    setup_s = start - t0
    in_window = {"compiles": compiles["n"],
                 "engine_builds": compile_cache.stats()["misses"] - misses0}
    peak = peak_bytes()
    del prog
    gc.collect()

    obs = Observation(setup_s=setup_s, window_s=end - start, sims=sims)
    if trace:
        found = sorted(tdir.rglob("*.xplane.pb"))
        if found:
            try:
                obs.trace = summarize(Trace.from_xplane(found[-1]))
            except ValueError as e:
                log(f"pimbench trace: {e}")
        shutil.rmtree(tdir, ignore_errors=True)

    checks = check.judge(cell, sims, expected if expected is not None
                         else cell.expected(), seed)
    run = {"sims": [{"data_seed": s.data_seed, "wall_s": s.end - s.start,
                     "issued": s.issued, "error": s.error} for s in sims],
           "window": {"setup_s": setup_s, "window_s": end - start,
                      **in_window, "memory_peak_bytes": peak}}
    for k, s in enumerate(run["sims"]):
        log(f"pimbench sim {k} " + json.dumps(s))
    log("pimbench window " + json.dumps(run["window"]))

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"]).read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": check.correct(checks), "attempted": len(sims),
              "failed": sum(s.error is not None for s in sims),
              "metrics": metrics, "device": device}
    if trace and obs.trace is not None:
        device["busy_s"] = obs.trace.busy_s
        device["window_s"] = obs.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in obs.trace.device_ops],
            "idle_gaps": [list(x) for x in obs.trace.idle_gaps]}
    result["run"] = run
    result["checks"] = checks
    return result
