"""Plain reference of the GEMVS traffic in HBM-PIM's all-bank layout:
y = A x over int32, per bank.

The inputs are drawn from the data seed as the workload draws them: A,
``(n_banks, rows, 64)``, then x, ``(n_banks, 64)``, each uniform in
[-64, 64).  A bank row holds ``hbm_lanes`` words, so the rows fall into
``groups = rows / hbm_lanes`` groups.  Bank row ``k * groups + g`` holds
column ``k`` of group ``g`` (A column-major); the accumulator of group
``g`` follows the matrix, at bank row ``64 * groups + g``.

x reaches the banks eight columns at a time through the eight SRF
registers.  The MACs of such a chunk, ``bank(acc g) += bank(A column,
group g) * srf(i)``, go out in launches of whole groups (eight MACs
each, one per SRF register) that fit ``hbm_crf_slots`` with their EXIT.
Commands are in the form of ``reference/hbm_bank.py``.
"""
import numpy as np

COLUMNS = 64        # PrIM's GEMV, 2K x 64 (arXiv:2105.03814, Table II)
SRF = 8


def _shape(dpu: dict, sizes: dict):
    """(banks, rows, lanes, groups)."""
    d, r, w = int(dpu["n_dpus"]), int(sizes["rows"]), int(dpu["hbm_lanes"])
    return d, r, w, r // w


def _plan(dpu: dict, sizes: dict):
    """Each launch as ``(chunk, first group, end group)``."""
    _, _, _, g = _shape(dpu, sizes)
    per = max(1, (int(dpu["hbm_crf_slots"]) - 1) // SRF)
    return [(c, g0, min(g0 + per, g)) for c in range(COLUMNS // SRF)
            for g0 in range(0, g, per)]


def _inputs(dpu: dict, sizes: dict, seed: int):
    d, r, _, _ = _shape(dpu, sizes)
    rng = np.random.default_rng(seed)
    a = rng.integers(-64, 64, (d, r, COLUMNS)).astype(np.int32)
    x = rng.integers(-64, 64, (d, COLUMNS)).astype(np.int32)
    return a, x


def _column_major(a: np.ndarray, w: int) -> np.ndarray:
    """A as bank words: word ``(k * groups + g) * w + l`` of bank ``d``
    is ``a[d, g * w + l, k]``."""
    d, r, c = a.shape
    return a.reshape(d, r // w, w, c).transpose(0, 3, 1, 2).reshape(d, -1)


def words(dpu: dict, sizes: dict) -> int:
    """Leading words of each bank that the reference predicts: the
    matrix, then the accumulators."""
    return (COLUMNS + 1) * int(sizes["rows"])


def image(dpu: dict, sizes: dict, seed: int) -> np.ndarray:
    """Expected leading bank words ``(n_banks, words)`` after the last
    launch: A column-major, then y."""
    _, _, w, _ = _shape(dpu, sizes)
    a, x = _inputs(dpu, sizes, seed)
    y = np.einsum("drc,dc->dr", a.astype(np.int64), x.astype(np.int64))
    return np.concatenate([_column_major(a, w), y.astype(np.int32)], axis=1)


def programs(dpu: dict, sizes: dict):
    """The command stream of each launch, in order."""
    _, _, _, g = _shape(dpu, sizes)
    acc = COLUMNS * g
    return [[("MAC", ("bank", acc + j), ("bank", (c * SRF + i) * g + j),
              ("srf", i)) for i in range(SRF) for j in range(g0, g1)]
            + [("EXIT",)] for c, g0, g1 in _plan(dpu, sizes)]


def launches(dpu: dict, sizes: dict, seed: int):
    """What each launch starts from: its commands, the SRF values
    ``(n_banks, 8)`` (x's columns of its chunk) and the bank words as
    segments ``[(first word, words (n_banks, n))]``, zero elsewhere: the
    matrix, one array that every launch shares, and the accumulators
    with the MACs of every launch before it."""
    d, r, w, g = _shape(dpu, sizes)
    a, x = _inputs(dpu, sizes, seed)
    matrix = _column_major(a, w)
    acc = np.zeros((d, r), np.int64)
    out = []
    for cmds, (c, g0, g1) in zip(programs(dpu, sizes), _plan(dpu, sizes)):
        cols = slice(c * SRF, (c + 1) * SRF)
        out.append((cmds, x[:, cols], [(0, matrix), (matrix.shape[1],
                                                     acc.astype(np.int32))]))
        rows = slice(g0 * w, g1 * w)
        acc[:, rows] += np.einsum("drc,dc->dr",
                                  a[:, rows, cols].astype(np.int64),
                                  x[:, cols].astype(np.int64))
    return out
