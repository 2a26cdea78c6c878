"""CPU tests of the benchmark's harness: cells, trace reduction, window,
and the plain DPU model that the check times the program against.

    python -m pytest tests/pimbench
"""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from pimbench import harness, spec, trace, window  # noqa: E402
from pimbench.reference import dpu as dpu_model  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
RECORDED = Path(__file__).parent / "data" / "tiny_trace.json"
DPU = json.loads((ROOT / "pimbench" / "configs" / "upmem-rank64.json")
                 .read_text())["dpu"]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = spec.load_cell(name)
    assert cell.config["dpu"]["n_dpus"] >= 1
    assert cell.traffic["workload"] and cell.traffic["seed_pool"] >= 1
    pins = cell.expected()
    assert sorted(map(int, pins)) == list(range(cell.traffic["seed_pool"]))
    ref = cell.reference()
    assert ref.words(cell.dpu(), cell.traffic["sizes"]) * 4 \
        <= cell.traffic["mram_bytes"]
    assert callable(ref.launches)
    t = cell.config["dpu"]["n_tasklets"]
    assert dpu_model.kernel(f"{cell.traffic['workload']}.t{t}")
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert {"setup_s", "sim_minstr_per_s"} <= names
    for m in names:
        assert callable(spec.metric_reader(m).read)


def test_configuration_files_are_distinct_and_named():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        doc = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(doc["reduced"])


def _brute_busy(tr: trace.Trace, a: int, b: int) -> int:
    """Busy nanoseconds of [a, b) by marking every nanosecond."""
    mark = np.zeros(b - a, bool)
    for _, st, en in tr.ops.values():
        for s, e in zip(st, en):
            mark[max(s, a) - a:max(min(e, b) - a, 0)] = True
    return int(mark.sum())


def test_trace_reduction_on_a_small_trace():
    tr = trace.Trace.from_rows(json.loads(RECORDED.read_text()))
    assert len(tr.ops) == 1, "one chip"
    sm = trace.summarize(tr)
    sims = [(s, e) for n, s, e in tr.spans if n == trace.SPAN_SIM]
    launches = [(s, e) for n, s, e in tr.spans if n == trace.SPAN_LAUNCH]
    a, b = sims[0][0], sims[-1][1]
    assert sm.window_s == pytest.approx((b - a) / 1e9)
    assert sm.busy_s == pytest.approx(_brute_busy(tr, a, b) / 1e9)
    assert len(sm.launch_busy_s) == len(launches) == 4
    for (s, e), busy in zip(launches, sm.launch_busy_s):
        assert busy == pytest.approx(_brute_busy(tr, s, e) / 1e9)
    # every executable of a launch starts inside its span
    _, mst, men = next(iter(tr.modules.values()))
    assert sum(sm.launch_module_s) == pytest.approx(
        float((men - mst).sum()) / 1e9)
    idle = sum(s for _, s in sm.idle_gaps)
    assert 0 < idle <= sm.window_s - sm.busy_s + 1e-12
    assert {k for k, _ in sm.idle_gaps} <= {
        "in_launch", "host_between_launches", "between_simulations"}
    top = [s for _, s in sm.device_ops]
    assert top == sorted(top, reverse=True) and len(top) <= 10


def test_union_overlap_and_gaps():
    st = np.array([10, 0, 12, 30, 31], np.int64)
    en = np.array([20, 5, 25, 32, 40], np.int64)
    ms, me = trace.union(st, en)
    assert ms.tolist() == [0, 10, 30] and me.tolist() == [5, 25, 40]
    assert trace.overlap(ms, me, 0, 50) == 5 + 15 + 10
    assert trace.overlap(ms, me, 3, 12) == 2 + 2
    assert trace.idle_gaps(ms, me, 0, 50) == [(5, 10), (25, 30), (40, 50)]
    assert trace.idle_gaps(ms, me, 12, 35) == [(25, 30)]


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_window_counts_the_simulation_in_flight():
    clock = _Clock()
    walls = [4.0, 4.0, 4.0, 4.0]

    def one(k):
        clock.t += walls[k]
        return SimpleNamespace(issued=1000 * (k + 1))

    t0, t1, items = window.closed_loop(one, 10.0, clock)
    # the third simulation is in flight at 10 s: it finishes and counts
    assert len(items) == 3 and (t0, t1) == (100.0, 112.0)
    assert window.rate([x.issued for x in items], t1 - t0) == 6000 / 12.0


def test_window_always_holds_one_simulation():
    clock = _Clock()

    def one(k):
        clock.t += 30.0
        return SimpleNamespace(issued=7)

    t0, t1, items = window.closed_loop(one, 0.0, clock)
    assert len(items) == 1 and t1 - t0 == 30.0


def test_data_seeds_order_the_same_pool():
    big = 2 ** 31 + 12345
    a, b = window.data_seeds(big, 16), window.data_seeds(big, 16)
    assert a.tolist() == b.tolist()
    assert sorted(a.tolist()) == list(range(16))
    assert window.data_seeds(7, 16).tolist() != a.tolist()


def test_require_chips_refuses_other_platforms():
    cpu = SimpleNamespace(platform="cpu")
    tpu = SimpleNamespace(platform="tpu")
    with pytest.raises(harness.NoChip):
        harness.require_chips([cpu], 1)
    with pytest.raises(harness.NoChip):
        harness.require_chips([], 1)
    with pytest.raises(harness.NoChip):
        harness.require_chips([tpu], 4)
    assert harness.require_chips([tpu] * 4, 4)


def test_run_refuses_the_cpu_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "pimbench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "not a TPU" in p.stderr


def _model(prog, tasklets=1, mram=None, **over):
    k = [tuple(x) for x in prog]
    return dpu_model.run(k, [], mram if mram is not None else [0] * 1024,
                         dpu={**DPU, **over}, tasklets=tasklets, dpu_id=0,
                         n_dpus=1)


def test_dpu_model_revolver_and_register_file_by_hand():
    add = ("ADD", 1, 2, 3, 0, False)          # reads r2 and r3: no hazard
    same = ("ADD", 1, 2, 4, 0, False)         # r2 and r4: same parity
    stop = ("STOP", 0, 0, 0, 0, False)
    # one tasklet issues every 11 cycles: 0, 11, 22; ends the cycle after
    assert _model([add, add, stop]) == (23, 3)
    assert _model([add, add, stop], revolver_cycles=10) == (21, 3)
    # two tasklets interleave: 0 and 1, 11 and 12, 22 and 23
    assert _model([add, add, stop], tasklets=2) == (24, 6)
    # a same-parity read holds the port for a cycle: the second tasklet
    # issues at 2, not 1
    assert _model([same, stop], tasklets=2) == (14, 4)
    mul = ("MUL", 1, 2, 3, 0, False)
    assert _model([mul, stop]) == (16, 2)     # 11 + 4 extra


def test_dpu_model_dma_by_hand():
    ldma = ("LDMA", 0, 0, 0, 8, True)          # 8 bytes from MRAM 0 to WRAM 0
    stop = ("STOP", 0, 0, 0, 0, False)
    # issued at 0, served from 1: a row miss, tRP + tRCD + tCL = 48 DRAM
    # cycles = 14 DPU cycles, plus 4 for 8 bytes; done at 19, the
    # tasklet issues STOP at 20
    assert _model([ldma, stop]) == (21, 2)
    # the second DMA, issued at 20 and served from 21, hits the open row:
    # tCL = 16 DRAM cycles = 5, plus 4; done at 30, STOP at 31
    assert _model([ldma, ldma, stop]) == (32, 3)
    mram = [7, 9] + [0] * 1022
    lw = ("LW", 5, 19, 0, 4, True)
    sdma = ("SDMA", 0, 0, 6, 8, True)          # WRAM 0 to MRAM r6 = 0
    out = list(mram)
    dpu_model.run([ldma, lw, ("ADD", 5, 5, 5, 0, False),
                   ("SW", 0, 19, 5, 0, True), sdma, stop], [], out,
                  dpu=DPU, tasklets=1, dpu_id=0, n_dpus=1)
    assert out[:2] == [18, 9]
