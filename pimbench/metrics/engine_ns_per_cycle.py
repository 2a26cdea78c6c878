"""Device time of the executables that the traced simulation's engine
launches ran, over the simulated cycles of those launches, in ns per
cycle (profiler trace).  Per simulated cycle, not per loop iteration, so
that packing several cycles into one iteration reads as a gain."""


def read(obs):
    t = obs.trace
    if t is None:
        return None
    cycles = [int(x.cycles.max()) for x in obs.sims[0].launches]
    device_s = sum(t.launch_module_s)
    if len(cycles) != len(t.launch_module_s) or not sum(cycles) \
            or not device_s:
        return None
    return 1e9 * device_s / sum(cycles)
