"""The closed loop and its arithmetic, apart from any simulator."""
from __future__ import annotations

import time
from typing import Callable, List, Tuple

import numpy as np


def data_seeds(seed: int, pool: int) -> np.ndarray:
    """The order in which a run walks the pool of data seeds 0..pool-1:
    every run seed gives the same set in another order."""
    return np.random.default_rng(int(seed) % (1 << 64)).permutation(pool)


def closed_loop(run_one: Callable[[int], object], seconds: float,
                clock: Callable[[], float] = time.perf_counter
                ) -> Tuple[float, float, List[object]]:
    """Call ``run_one(0), run_one(1), ...`` back to back until ``seconds``
    have passed at the end of a call.  The call in flight at that moment
    finishes and counts.  Returns ``(window_start, window_end, items)``."""
    t0 = clock()
    items = []
    while True:
        items.append(run_one(len(items)))
        end = clock()
        if end - t0 >= seconds:
            return t0, end, items


def rate(work: List[float], window_s: float) -> float:
    """All the work of the window over all of its time."""
    return sum(work) / window_s
