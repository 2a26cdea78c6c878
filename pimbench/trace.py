"""Reduce a profiler trace to device busy time, idle gaps and the device
time of each launch.

A trace is kept as a :class:`Trace`: per device, the intervals of its
operations and of its executables (XLA modules), and the benchmark's own
host spans (``pimbench.sim`` around each simulation, ``pimbench.launch``
around each engine launch).  All times are nanoseconds on the profiler's
clock, which device and host events share.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

SPAN_SIM = "pimbench.sim"
SPAN_LAUNCH = "pimbench.launch"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Events = Tuple[List[str], np.ndarray, np.ndarray]   # names, start, end


def _events(rows) -> Events:
    names = [r[0] for r in rows]
    start = np.array([r[1] for r in rows], np.int64)
    end = start + np.array([r[2] for r in rows], np.int64)
    return names, start, end


@dataclass
class Trace:
    ops: Dict[str, Events] = field(default_factory=dict)      # per device
    modules: Dict[str, Events] = field(default_factory=dict)  # per device
    spans: List[Tuple[str, int, int]] = field(default_factory=list)

    @classmethod
    def from_rows(cls, doc: dict) -> "Trace":
        """From the JSON form: ``{"ops": {device: [[name, start, dur]]},
        "modules": {...}, "spans": [[name, start, end]]}``."""
        return cls(ops={d: _events(r) for d, r in doc["ops"].items()},
                   modules={d: _events(r) for d, r in doc["modules"].items()},
                   spans=[(n, int(s), int(e)) for n, s, e in doc["spans"]])

    @classmethod
    def from_xplane(cls, path: Path) -> "Trace":
        """Read an ``.xplane.pb`` file written by ``jax.profiler``."""
        import jax
        prof = jax.profiler.ProfileData.from_file(str(path))
        ops, modules, spans = {}, {}, []
        for plane in prof.planes:
            device = (plane.name.startswith("/device:")
                      and not plane.name.startswith("/device:CPU"))
            for line in plane.lines:
                if device and line.name in (OPS_LINE, MODULES_LINE):
                    rows = [(ev.name, ev.start_ns, ev.duration_ns)
                            for ev in line.events]
                    (ops if line.name == OPS_LINE else modules)[
                        plane.name] = _events(rows)
                elif plane.name.startswith("/host:"):
                    for ev in line.events:
                        if ev.name.startswith("pimbench."):
                            s = int(ev.start_ns)
                            spans.append((ev.name, s, s + int(ev.duration_ns)))
        return cls(ops=ops, modules=modules, spans=sorted(spans,
                                                          key=lambda x: x[1]))


def union(start: np.ndarray, end: np.ndarray):
    """Merge intervals into sorted disjoint ``(start, end)`` arrays."""
    if len(start) == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(first)
    return s[idx], np.maximum.reduceat(e, idx)


def overlap(ms: np.ndarray, me: np.ndarray, a: int, b: int) -> int:
    """Nanoseconds of the disjoint intervals ``(ms, me)`` inside [a, b)."""
    return int(np.clip(np.minimum(me, b) - np.maximum(ms, a), 0, None).sum())


def idle_gaps(ms: np.ndarray, me: np.ndarray, a: int, b: int):
    """``(start, end)`` of every idle stretch of [a, b) between the
    disjoint busy intervals ``(ms, me)``."""
    keep = (me > a) & (ms < b)
    s, e = np.clip(ms[keep], a, b), np.clip(me[keep], a, b)
    edges_s = np.concatenate([[a], e])
    edges_e = np.concatenate([s, [b]])
    gap = edges_e > edges_s
    return list(zip(edges_s[gap].tolist(), edges_e[gap].tolist()))


@dataclass
class Summary:
    """What the per-layer metrics read from one traced stretch."""

    window_s: float             # first traced simulation's start to last end
    busy_s: float               # union of device operations, mean over chips
    launch_span_s: List[float]  # host span of each traced launch
    launch_busy_s: List[float]  # device busy inside each launch span
    launch_module_s: List[float]  # device time of executables started inside
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def _label(mid: int, sims, launches) -> str:
    if any(s <= mid < e for s, e in launches):
        return "in_launch"
    if any(s <= mid < e for s, e in sims):
        return "host_between_launches"
    return "between_simulations"


def summarize(tr: Trace, top: int = 10) -> Summary:
    """Reduce a trace that holds at least one ``pimbench.sim`` span and
    the operations of at least one device."""
    sims = [(s, e) for n, s, e in tr.spans if n == SPAN_SIM]
    launches = [(s, e) for n, s, e in tr.spans if n == SPAN_LAUNCH]
    if not sims or not tr.ops:
        raise ValueError("trace holds no simulation span or no device op")
    w0, w1 = min(s for s, _ in sims), max(e for _, e in sims)
    busy, per_launch, per_module, gaps = [], [], [], []
    by_op: Dict[str, float] = {}
    for dev in sorted(tr.ops):
        names, st, en = tr.ops[dev]
        ms, me = union(st, en)
        busy.append(overlap(ms, me, w0, w1))
        per_launch.append([overlap(ms, me, a, b) for a, b in launches])
        inside = np.clip(np.minimum(en, w1) - np.maximum(st, w0), 0, None)
        for name, ns in zip(names, inside.tolist()):
            if ns:
                by_op[name] = by_op.get(name, 0) + ns
        gaps += [(_label((a + b) // 2, sims, launches), b - a)
                 for a, b in idle_gaps(ms, me, w0, w1)]
        _, mst, men = tr.modules.get(dev, ([], np.zeros(0, np.int64),
                                           np.zeros(0, np.int64)))
        per_module.append([int((men - mst)[(mst >= a) & (mst < b)].sum())
                           for a, b in launches])
    n = len(tr.ops)
    mean = lambda rows: [sum(c) / n / 1e9 for c in zip(*rows)]  # noqa: E731
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(busy) / n / 1e9,
        launch_span_s=[(b - a) / 1e9 for a, b in launches],
        launch_busy_s=mean(per_launch),
        launch_module_s=mean(per_module),
        device_ops=[(k, v / n / 1e9) for k, v in ops],
        idle_gaps=[(k, v / 1e9) for k, v in sorted(gaps,
                                                   key=lambda g: -g[1])[:top]])
