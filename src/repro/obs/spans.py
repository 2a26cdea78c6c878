"""Host spans: where the simulator spends host wall-clock time.

Unlike :class:`~repro.obs.tracer.Tracer`, which records *modelled* time
(the simulated schedule, never the wall clock), these spans measure the
host's own wall clock at the simulator's layer boundaries.  They are
``jax.profiler.TraceAnnotation`` events, so they land in the same
profiler trace (``.xplane.pb``, viewable in Perfetto) as the device's
operations, on one clock, and each stretch in which the device is idle
can be put down to what the host was doing.

With no profiler running a span costs about a microsecond and records
nothing.  Spans nest along the call tree; their args are small scalars.

The spans, from the outside in (args in brackets):

* ``repro.sim`` — one ``Workload.run`` [workload, seed, sim_id];
* ``repro.launch`` — one ``compile_cache`` launch [sim_id, backend,
  dpus, cache: ``hit`` or ``miss``], and its four parts:
  ``repro.launch.prepare`` (validation, bucketing, the host state, the
  executable lookup), ``repro.launch.upload`` (state and instruction
  image to the device) [nbytes], ``repro.launch.device`` (the engine
  executable, to completion), ``repro.launch.readback`` (the final
  state back to host numpy) [nbytes];
* ``repro.host.report`` — ``PIMSystem``'s kernel report and pricing;
* ``repro.comm.collective`` — one ``repro.comm`` collective [kind];
* ``repro.comm.transfer`` — one host transfer's pricing [kind];
* ``repro.sched.sync`` — ``PIMSystem.sync``'s schedule resolution.

``sim_id`` is a process-wide count of simulations; the spans of one
simulation share it (a launch outside any simulation reads -1).  A
``Workload.run`` inside another (the batches of a pipelined run) is part
of the outer simulation.
"""
from __future__ import annotations

import contextvars
import itertools
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

SIM = "repro.sim"
LAUNCH = "repro.launch"
LAUNCH_PREPARE = "repro.launch.prepare"
LAUNCH_UPLOAD = "repro.launch.upload"
LAUNCH_DEVICE = "repro.launch.device"
LAUNCH_READBACK = "repro.launch.readback"
HOST_REPORT = "repro.host.report"
COMM_COLLECTIVE = "repro.comm.collective"
COMM_TRANSFER = "repro.comm.transfer"
SCHED_SYNC = "repro.sched.sync"

_SIM_IDS = itertools.count()
_SIM_ID = contextvars.ContextVar("repro_sim_id", default=-1)


def span(name: str, **args) -> TraceAnnotation:
    """A host span named ``name`` (one of this module's constants)."""
    return TraceAnnotation(name, **args)


def sim_id() -> int:
    """The id of the simulation in progress, -1 outside any."""
    return _SIM_ID.get()


@contextmanager
def simulation(workload: str, seed: int):
    """The ``repro.sim`` span of one simulation, under a fresh id.  A
    simulation started inside another (a pipelined run's batches) is
    part of it: no new span, the outer id."""
    if _SIM_ID.get() >= 0:
        yield _SIM_ID.get()
        return
    sid = next(_SIM_IDS)
    token = _SIM_ID.set(sid)
    try:
        with span(SIM, workload=workload, seed=int(seed), sim_id=sid):
            yield sid
    finally:
        _SIM_ID.reset(token)
