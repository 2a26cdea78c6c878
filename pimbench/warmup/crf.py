"""Warm-up of a cell whose launches are HBM-PIM command streams
(``repro.core.hbmpim.launch_commands``: all banks in lockstep, one
thread, a CRF image of ``hbm_crf_slots`` slots): the command engine's
executable for each program length that the workload's reference
launches, at the configuration's MRAM width.

The executable depends on the image's length bucket, not on its
commands, so each warm-up image holds as many EXITs as the launch holds
commands."""


def prewarm(prog) -> list:
    """Compile (or load) the executables; returns their compile-cache
    keys, one per program length."""
    from repro.core import compile_cache, hbmpim
    cfg = prog.cfg
    lengths = {len(cmds) for cmds in
               prog.reference.programs(prog.dpu, prog.traffic["sizes"])}
    keys = []
    for n in sorted(lengths):
        image = hbmpim.CrfProgram()
        for _ in range(n):
            image.exit_()
        keys.append(compile_cache.prewarm(
            cfg, image.binary(cfg.hbm_crf_slots), mram_words=cfg.mram_words,
            n_threads=1, backend="hbmpim_cmd"))
    return keys
