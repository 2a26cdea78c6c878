"""Process start to the start of the first timed simulation: imports,
device start-up, the persistent compile cache and the warm-up of the
cell's own engine executable (host clock)."""


def read(obs):
    return obs.setup_s
