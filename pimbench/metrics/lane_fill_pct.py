"""Share of the engine's lane-cycles that simulate a live DPU, in
percent: the real DPUs' cycles over the lane count times the slowest
DPU's cycles, summed over the process's engine launches (a count, from
``compile_cache.stats()``; in a ``run.py`` process those are the run's
launches).  Padded lanes of the DPU bucket and lanes whose DPU finished
early lower it.  None where the program keeps no such counters."""


def read(obs):
    try:
        from repro.core import compile_cache
    except ImportError:
        return None
    s = compile_cache.stats()
    lanes, live = s.get("lane_cycles"), s.get("dpu_cycles")
    if not lanes or live is None:
        return None
    return 100.0 * live / lanes
