"""The benchmark's check catches what it is there to catch: CPU runs of
small cells with the timed path broken underneath, and with the control
in the program's place.  Each drives the rest of a run (set-up, the
window, the references) without looking for a chip, and sees ``correct``
come out false.

    python -m pytest tests/pimbench
"""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from pimbench import control, harness, spec  # noqa: E402

CONFIG = json.loads(
    (ROOT / "pimbench" / "configs" / "upmem-rank64.json").read_text())
DPU = {**CONFIG["dpu"], "n_dpus": 4}
SMALL = {
    "VA": dict(scale=0.02, mram_bytes=64 << 10,
               sizes={"elements_per_dpu": 288}),
    "BFS": dict(scale=0.02, mram_bytes=1 << 20, sizes={"vertices": 96}),
}


def small_cell(workload: str) -> spec.Cell:
    return spec.Cell(
        name=f"small.{workload}", chips=1, config={"dpu": dict(DPU)},
        traffic={"workload": workload, "seed_pool": 2, **SMALL[workload]},
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": "sim_minstr_per_s", "unit": "Minstr/s"}],
        per_layer=[])


@pytest.fixture(scope="module", params=sorted(SMALL))
def pinned(request):
    """A small cell and its statistics pinned from sound runs."""
    cell = small_cell(request.param)
    prog = harness.Program(cell)
    prog.prewarm()
    pins = {}
    for ds in range(cell.traffic["seed_pool"]):
        sim = harness.Sim(data_seed=ds)
        prog.simulate(sim)
        assert sim.error is None
        pins[str(ds)] = sim.stats
    return cell, pins


def _run(cell, pins, **kw):
    r = harness.measure(cell, 12345, 0.0, False, t0=time.perf_counter(),
                        expected=pins, log=lambda _: None, **kw)
    return r, {k: v["value"] for k, v in r["checks"].items()}


def _break_launches(monkeypatch, mutate):
    """Pass every engine launch's state through ``mutate(out, mram_in)``."""
    from repro.core import compile_cache
    inner = compile_cache.run

    def run(cfg, binary, wram, mram, *args, **kw):
        out = {k: np.array(v) for k, v in
               inner(cfg, binary, wram, mram, *args, **kw).items()}
        mutate(out, np.asarray(mram))
        return out

    monkeypatch.setattr(compile_cache, "run", run)


def _unchanged(out, mram):
    out["mram"][:] = mram
    out["cycle"][:] = 0
    out["c_issued"][:] = 0


def _half_left_out(out, mram):
    h = out["mram"].shape[0] // 2
    out["mram"][h:] = mram[h:]
    out["cycle"][h:] = 0
    out["c_issued"][h:] = 0


def _answer_altered(out, mram):
    out["mram"][1, 0] += 1


def _cycles_altered(out, mram):
    out["cycle"][:] += 1


def test_sound_run_is_correct(pinned):
    r, checks = _run(*pinned)
    assert r["correct"] and r["attempted"] >= 1 and r["failed"] == 0
    assert checks == {"failed_simulations": 0, "output_words_wrong": 0,
                      "timing_wrong": 0, "statistics_wrong": 0}
    assert r["metrics"]["sim_minstr_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _answer_altered, _cycles_altered])
def test_broken_launch_is_not_correct(pinned, monkeypatch, fault):
    _break_launches(monkeypatch, fault)
    r, checks = _run(*pinned)
    assert not r["correct"]
    assert max(checks.values()) > 0


@pytest.mark.parametrize("pinned", ["BFS"], indirect=True)
def test_exchange_left_out_is_not_correct(pinned, monkeypatch):
    cell, pins = pinned
    from repro.comm import collectives
    monkeypatch.setattr(collectives, "allreduce",
                        lambda system, buf, *a, **kw: None)
    r, checks = _run(cell, pins)
    assert not r["correct"]


def test_control_is_not_correct(pinned):
    r, checks = _run(*pinned, dpu_override=control.CONTROL)
    assert not r["correct"]
    assert checks["timing_wrong"] > 0, "the plain DPU model sees it"
    assert checks["output_words_wrong"] == 0, "the control computes right"
