"""Host time of one simulation outside its engine launches: the wall of
``Workload.run`` less the walls of the ``compile_cache.run`` calls inside
it, averaged over the window's simulations after the profiled first one,
or over that one where none came after (the benchmark's spans)."""


def read(obs):
    sims = obs.sims[1:] or obs.sims
    outside = [s.end - s.start - sum(x.end - x.start for x in s.launches)
               for s in sims]
    return 1e3 * sum(outside) / len(outside)
