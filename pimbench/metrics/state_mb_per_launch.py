"""Bytes of the simulator state that one engine launch returns to the
host, in MB, averaged over the window's launches (a count)."""


def read(obs):
    launches = [x for s in obs.sims for x in s.launches]
    if not launches:
        return None
    return sum(x.state_bytes for x in launches) / len(launches) / 1e6
