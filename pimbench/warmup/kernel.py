"""Warm-up of a cell whose launches run the workload's own DPU kernel
(the UPMEM engines): its one engine executable, for the kernel as the
workload builds it at the configuration's tasklets."""


def prewarm(prog) -> list:
    """Compile (or load) the executable; returns its compile-cache key."""
    t = prog.cfg.n_tasklets
    binary = prog.workload.build(t).binary(prog.cfg.iram_instrs)
    return [prog.PIMSystem(prog.cfg).prewarm(binary, n_threads=t)]
