"""Plain reference of the BFS traffic: levels from vertex 0 by a queue.

The graph is drawn from the data seed as the workload draws it: a degree
in [2, 14) per vertex, then every edge target uniform over the vertices.
Every DPU holds the whole graph.  After the last level's kernel, which
finds no new vertex, each DPU's MRAM holds, at word offsets:

* 0: the row pointers (``vertices + 1`` words);
* ``oa``: the edge targets;
* ``od``: the BFS level of every vertex (-1 where unreachable);
* ``oc``: the last frontier given to the kernel (1 for the vertices of
  the deepest level, else 0);
* ``on``: the next frontier, all zero;

with ``oa`` rounded up to an even word and ``od``/``oc``/``on`` regions
padded to 256-word DMA blocks.  Everything else in the prefix is zero.
"""
from collections import deque

import numpy as np


def _layout(v: int, e: int):
    pad = (v + 255) // 256 * 256
    oa = (v + 3) // 2 * 2
    od = oa + (e + 255) // 256 * 256
    oc = od + pad
    on = oc + pad
    return oa, od, oc, on, on + pad


def _graph(v: int, seed: int):
    rng = np.random.default_rng(seed)
    deg = rng.integers(2, 14, v)
    rowptr = np.zeros(v + 1, np.int64)
    rowptr[1:] = deg.cumsum()
    adj = rng.integers(0, v, int(rowptr[-1])).astype(np.int64)
    return rowptr, adj


def _levels(v: int, rowptr, adj) -> np.ndarray:
    dist = [-1] * v
    dist[0] = 0
    todo = deque([0])
    while todo:
        x = todo.popleft()
        for u in adj[rowptr[x]:rowptr[x + 1]]:
            if dist[u] < 0:
                dist[u] = dist[x] + 1
                todo.append(int(u))
    return np.array(dist, np.int64)


def words(dpu: dict, sizes: dict) -> int:
    """Leading MRAM words of each DPU that the reference predicts.  The
    edge count depends on the graph, so the bound covers the most edges
    any graph of this size can have."""
    v = int(sizes["vertices"])
    return _layout(v, 13 * v)[-1]


def image(dpu: dict, sizes: dict, seed: int) -> np.ndarray:
    """Expected leading MRAM words ``(n_dpus, words)`` after the last
    level's kernel."""
    v = int(sizes["vertices"])
    rowptr, adj = _graph(v, seed)
    dist = _levels(v, rowptr, adj)
    oa, od, oc, on, _ = _layout(v, len(adj))
    row = np.zeros(words(dpu, sizes), np.int64)
    row[:v + 1] = rowptr
    row[oa:oa + len(adj)] = adj
    row[od:od + v] = dist
    row[oc:oc + v] = dist == dist.max()
    return np.tile(row.astype(np.int32), (int(dpu["n_dpus"]), 1))


def launches(dpu: dict, sizes: dict, seed: int):
    """What each kernel launch starts from: per level ``k`` = 1, 2, ...
    up to one past the deepest, the arguments (``(n_dpus, 9)``: the
    padded vertex count, the level, the byte offsets of the row
    pointers, edges, levels, frontier and next frontier, and the DPU's
    own vertex range) and the MRAM words (``(n_dpus, mram words)``: the
    graph, the levels found before ``k``, the frontier of level
    ``k - 1``)."""
    v, d = int(sizes["vertices"]), int(dpu["n_dpus"])
    rowptr, adj = _graph(v, seed)
    dist = _levels(v, rowptr, adj)
    oa, od, oc, on, _ = _layout(v, len(adj))
    pad = (v + 255) // 256 * 256
    per = v // d
    own = [(k * per, v if k == d - 1 else (k + 1) * per) for k in range(d)]
    out = []
    for level in range(1, int(dist.max()) + 2):
        row = np.zeros(int(dpu["mram_bytes"]) // 4, np.int64)
        row[:v + 1] = rowptr
        row[oa:oa + len(adj)] = adj
        row[od:od + v] = np.where((dist >= 0) & (dist < level), dist, -1)
        row[oc:oc + v] = dist == level - 1
        args = np.array([[pad, level, 0, 4 * oa, 4 * od, 4 * oc, 4 * on, a, b]
                         for a, b in own], np.int32)
        out.append((args, np.tile(row.astype(np.int32), (d, 1))))
    return out
