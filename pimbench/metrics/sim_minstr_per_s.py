"""Simulated DPU instructions issued (summed over DPUs and launches) by
every simulation of the window, over the window's wall seconds, in
millions (host clock)."""
from pimbench.window import rate


def read(obs):
    return rate([s.issued for s in obs.sims], obs.window_s) / 1e6
