"""CPU tests of the 2,560-DPU server configuration and of ``lane_fill_pct``:
a small cell cut from the server's configuration against the references,
and the share of engine lane-cycles that simulate a live DPU.

    python -m pytest tests/pimbench/test_pimbench_server.py
"""
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from pimbench import harness, spec, window  # noqa: E402
from repro.core import compile_cache  # noqa: E402
from repro.core.config import DPUConfig  # noqa: E402

SEED = 2 ** 31 + 271828
FILL = spec.metric_reader("lane_fill_pct")


def _config(name: str) -> dict:
    return json.loads((ROOT / "pimbench" / "configs" / f"{name}.json")
                      .read_text())


def _cut(cell: spec.Cell, traffic=None, **dpu) -> spec.Cell:
    """``cell`` at fewer DPUs (and another traffic mix, where given)."""
    cell = copy.copy(cell)
    cell.config = copy.deepcopy(cell.config)
    cell.config["dpu"].update(dpu)
    cell.traffic = {**cell.traffic, **(traffic or {})}
    return cell


def _fill_by_hand(launches) -> float:
    """Real DPUs' cycles over the lane bucket times the slowest DPU's."""
    live = sum(int(x.cycles.sum()) for x in launches)
    lanes = sum(compile_cache.dpu_bucket(len(x.cycles)) * int(x.cycles.max())
                for x in launches)
    return 100.0 * live / lanes


def test_server_config_is_the_rank_at_server_width():
    server, rank = _config("upmem-server2560"), _config("upmem-rank64")
    assert {**rank["dpu"], "n_dpus": 2560, "n_ranks": 40} == server["dpu"]
    cfg = DPUConfig(**spec.load_cell("server2560.va").dpu())
    assert cfg.n_dpus // cfg.n_ranks == 64
    assert compile_cache.dpu_bucket(cfg.n_dpus) == 4096


@pytest.fixture(scope="module")
def small_server():
    """The server cell at 10 DPUs over 5 ranks, padded to 16 lanes (the
    server's 2,560 of 4,096): one traced simulation through the
    harness, with its data seed's statistics pinned by a run before."""
    cell = _cut(spec.load_cell("server2560.va"), n_dpus=10, n_ranks=5)
    ds = int(window.data_seeds(SEED, int(cell.traffic["seed_pool"]))[0])
    pin = harness.Sim(data_seed=ds)
    harness.Program(cell).simulate(pin)
    assert pin.error is None, pin.error
    compile_cache.clear()           # the counters now hold the run alone
    result = harness.measure(cell, SEED, 0.0, True, t0=time.perf_counter(),
                             expected={str(ds): pin.stats},
                             log=lambda _: None)
    return pin, result, compile_cache.stats()


def test_small_server_cell_matches_the_references(small_server):
    pin, result, _ = small_server
    assert result["attempted"] == 1 and result["failed"] == 0
    assert {k: c["value"] for k, c in result["checks"].items()} == {
        "failed_simulations": 0, "output_words_wrong": 0,
        "timing_wrong": 0, "statistics_wrong": 0}
    assert result["correct"]
    assert pin.stats["launches"] == 1


def test_small_server_cell_fills_five_eighths_of_its_lanes(small_server):
    pin, result, s = small_server
    assert result["metrics"]["lane_fill_pct"] == {"value": 62.5, "unit": "%"}
    assert _fill_by_hand(pin.launches) == 62.5
    assert (s["lane_cycles"], s["dpu_cycles"]) == \
        (16 * int(pin.launches[0].cycles.max()),
         int(pin.launches[0].cycles.sum()))


def test_uneven_bfs_lanes_read_below_full():
    cell = _cut(spec.load_cell("rank64.bfs"),
                {"scale": 0.02, "mram_bytes": 1 << 16}, n_dpus=4)
    sim = harness.Sim(data_seed=0)
    compile_cache.clear()
    harness.Program(cell).simulate(sim)
    assert sim.error is None, sim.error
    assert len(sim.launches) > 1
    ends = np.array([x.cycles for x in sim.launches])
    assert (ends != ends.max(1, keepdims=True)).any(), "DPUs finish unevenly"
    fill = FILL.read(None)
    assert fill == pytest.approx(_fill_by_hand(sim.launches), rel=1e-12)
    assert 50.0 < fill < 100.0


@pytest.mark.parametrize("stats", [
    {"entries": 1, "launches": 2, "loop_iters": 30},   # a program without them
    {"lane_cycles": 0, "dpu_cycles": 0},               # no launch ran
])
def test_lane_fill_reads_nothing_without_lane_cycles(monkeypatch, stats):
    monkeypatch.setattr(compile_cache, "stats", lambda: stats)
    assert FILL.read(None) is None

