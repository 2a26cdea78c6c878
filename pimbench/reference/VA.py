"""Plain reference of the VA traffic: C = A + B over int32, per DPU.

The inputs are drawn from the data seed as the workload draws them: A,
then B, each ``(n_dpus, elements_per_dpu)`` uniform in [-1000, 1000).
The final MRAM image of each DPU is A, B and C packed back to back, each
padded to an even number of words.
"""
import numpy as np


def _stride(n: int) -> int:
    return (n + 1) // 2 * 2


def words(dpu: dict, sizes: dict) -> int:
    """Leading MRAM words of each DPU that the reference predicts."""
    return 3 * _stride(int(sizes["elements_per_dpu"]))


def image(dpu: dict, sizes: dict, seed: int) -> np.ndarray:
    """Expected leading MRAM words ``(n_dpus, words)`` after the kernel."""
    d, n = int(dpu["n_dpus"]), int(sizes["elements_per_dpu"])
    rng = np.random.default_rng(seed)
    a = rng.integers(-1000, 1000, (d, n)).astype(np.int32)
    b = rng.integers(-1000, 1000, (d, n)).astype(np.int32)
    s = _stride(n)
    img = np.zeros((d, 3 * s), np.int32)
    img[:, :n] = a
    img[:, s:s + n] = b
    img[:, 2 * s:2 * s + n] = a + b
    return img


def launches(dpu: dict, sizes: dict, seed: int):
    """What each kernel launch starts from: one launch, whose arguments
    (``(n_dpus, 4)``: elements, then the byte offsets of A, B and C) and
    MRAM words (``(n_dpus, mram words)``: A and B, C zero) it returns."""
    d, n = int(dpu["n_dpus"]), int(sizes["elements_per_dpu"])
    img = image(dpu, sizes, seed)
    s = _stride(n)
    img[:, 2 * s:] = 0
    mram = np.zeros((d, int(dpu["mram_bytes"]) // 4), np.int32)
    mram[:, :img.shape[1]] = img
    args = np.tile(np.array([n, 0, 4 * s, 8 * s], np.int32), (d, 1))
    return [(args, mram)]
