"""CPU tests of ``pimbench/split.py``: the program's spans read from a small
trace in the profiler's layout, each reading and the idle attribution
checked by hand.

    python -m pytest tests/pimbench/test_pimbench_split.py
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from pimbench import harness, spec, split, trace  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "tiny_program_trace.json"
US = 1e-6


@pytest.fixture
def recorded():
    doc = json.loads(RECORDED.read_text())
    return trace.Trace.from_rows(doc), split.spans_from_rows(
        doc["program_spans"])


def _sim():
    """The traced simulation: each launch's per-DPU cycles, and each
    launch's ``(loop_iters, h2d_bytes)``."""
    def launch(cycles):
        return harness.Launch(0.0, 0.0, 0, np.array(cycles), np.zeros(2))
    sim = harness.Sim(data_seed=0, launches=[launch([100, 90]),
                                             launch([70, 80])])
    return sim, [(40, 1000), (30, 1000)]


def test_program_spans_keep_their_args(recorded):
    _, spans = recorded
    assert spans[0].name == split.SIM and spans[0].args["sim_id"] == 4
    launches = [s for s in spans if s.name == "repro.launch"]
    assert [s.args["cache"] for s in launches] == ["hit", "hit"]
    assert [s.args["nbytes"] for s in spans
            if s.name == "repro.launch.upload"] == [1000, 1000]
    # sorted by start, a parent before the child that starts with it
    assert [(s.start, -s.end) for s in spans] == sorted(
        (s.start, -s.end) for s in spans)


def test_idle_by_span_by_hand(recorded):
    tr, spans = recorded
    idle = split.idle_by_span(tr, spans)
    # the device is busy 3 us in the first upload, 30 + 44 of the first
    # launch's 80 us on the device, 58 of the second's 60; every other
    # stretch of the 400 us simulation is idle, and belongs to the
    # innermost span over it
    want = {"repro.sim": 10 + 10 + 20 + 10 + 5 + 20,
            "repro.launch.readback": 30 + 30,
            "repro.launch.prepare": 20 + 30,
            "repro.launch.upload": 10 - 3 + 20,
            "repro.host.report": 10 + 10,
            "repro.comm.collective": 10,
            "repro.sched.sync": 10,
            "repro.launch.device": 80 - 74 + 60 - 58,
            "repro.comm.transfer": 5}
    assert idle.keys() == want.keys()
    for k, v in want.items():
        assert idle[k] == pytest.approx(v * US), k
    busy = 3 + 30 + 44 + 58
    assert sum(idle.values()) == pytest.approx((400 - busy) * US)
    assert list(idle.values()) == sorted(idle.values(), reverse=True)


def test_readings_by_hand(recorded):
    tr, spans = recorded
    sim, counters = _sim()
    m = split.readings(tr, spans, sim, counters)
    assert m == pytest.approx({
        # means over the traced simulation's two launches, in ms
        "launch_prepare_ms": (20 + 30) / 2 * 1e-3,
        "launch_upload_ms": (10 + 20) / 2 * 1e-3,
        "launch_readback_ms": (30 + 30) / 2 * 1e-3,
        # 2,000 bytes over 2 launches
        "upload_mb_per_launch": 2000 / 2 / 1e6,
        # 70 iterations over max cycles 100 + 80
        "engine_iters_per_cycle": 70 / 180,
        # 78 + 58 us of jit_pim_engine over 70 iterations
        "engine_us_per_iter": (78 + 58) / 70,
        # report 10 + 10, collective 10, transfer 5, sync 10
        "host_runtime_ms_per_sim": 45e-3,
        # the simulation's 400 us less its children's union: two
        # launches of 140 and 45 of host runtime
        "workload_host_ms_per_sim": (400 - 2 * 140 - 45) * 1e-3})


def test_consistency_against_the_harness_readers(recorded):
    tr, spans = recorded
    sim, counters = _sim()
    m = split.readings(tr, spans, sim, counters)
    sm = trace.summarize(tr)
    obs = harness.Observation(setup_s=0.0, window_s=1.0, sims=[sim],
                              trace=sm)
    h = {n: spec.metric_reader(n).read(obs) for n in split.HARNESS_METRICS}
    # harness: device time in the launch spans over the traced cycles
    assert h["engine_ns_per_cycle"] == pytest.approx(136e3 / 180)
    # harness: each launch span less the device's busy time inside it
    assert h["launch_host_ms"] == pytest.approx((150 - 77 + 150 - 58) / 2e3)
    idle = split.idle_by_span(tr, spans)
    c = split.consistency(m, h, idle)
    # both sides read the same launches: the identity is exact
    assert c["engine_identity"] == pytest.approx(1.0)
    assert c["launch_parts_over_launch_host"] == pytest.approx(
        0.070 / 0.0825)
    assert c["idle_below_sim_share"] == pytest.approx(1 - 75 / 265)


def test_a_program_without_spans_or_counters_reads_nothing(recorded):
    tr, _ = recorded
    sim, _ = _sim()
    assert split.readings(tr, [], sim, [(0, 0), (0, 0)]) == {}
    assert split.idle_by_span(tr, []) == {}
    assert split.consistency({}, {}, {}) == {}


def test_counter_probe_reads_zero_where_the_program_counts_nothing(
        monkeypatch):
    from repro.core import compile_cache
    box = {"loop_iters": 0, "h2d_bytes": 0}

    def run(*args, **kw):
        box["loop_iters"] += 7
        box["h2d_bytes"] += 64
        return {}

    monkeypatch.setattr(compile_cache, "run", run)
    monkeypatch.setattr(compile_cache, "stats", lambda: dict(box))
    got = []
    undo = split.probe_counters(got)
    compile_cache.run()
    compile_cache.run()
    undo()
    assert got == [(7, 64), (7, 64)] and compile_cache.run is run
    monkeypatch.setattr(compile_cache, "stats", lambda: {"hits": 0})
    undo = split.probe_counters(got)
    compile_cache.run()
    undo()
    assert got[-1] == (0, 0)
