"""CPU tests of the benchmark's harness: cells, trace reduction, window,
warm-up, and the plain timing models (the UPMEM DPU, the HBM-PIM bank)
that the check times the program against.

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
from pimbench.reference import hbm_bank  # noqa: E402

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
    model = cell.timing_model()
    assert callable(model.time_launch)
    if hasattr(model, "kernel"):                 # it runs a kernel file
        t = cell.config["dpu"]["n_tasklets"]
        assert model.kernel(f"{cell.traffic['workload']}.t{t}")
    assert callable(cell.warmup().prewarm)
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


@pytest.mark.parametrize("name", CELLS)
def test_warmup_keeps_the_kernel_prewarm_key(name, monkeypatch):
    """The warm-up that each cell's configuration names builds the
    compile-cache key of the workload's kernel prewarmed at the
    configuration's tasklets.  Nothing is compiled: the launch is cut to
    the key."""
    from repro.core import compile_cache

    def launch(cfg, binary, wram, mram, T, be, pad, **_):
        P = compile_cache.program_bucket(binary.n_instrs,
                                         binary.opcode.shape[0])
        entry, hit = compile_cache._get_entry(
            cfg, be, P, compile_cache._lanes(cfg, pad), T, mram.shape[1])
        return entry, hit, None, 0

    monkeypatch.setattr(compile_cache, "_launch", launch)
    monkeypatch.setattr(compile_cache, "_ENTRIES", {})
    prog = harness.Program(spec.load_cell(name))
    t = prog.cfg.n_tasklets
    binary = prog.workload.build(t).binary(prog.cfg.iram_instrs)
    want = prog.PIMSystem(prog.cfg).prewarm(binary, n_threads=t)
    assert prog.prewarm() == [want]
    assert want[0] == "scalar" and want[3:5] == (
        compile_cache.dpu_bucket(prog.cfg.n_dpus), t)


def _hbm_dpu(**over) -> dict:
    """The rank's DPU block on the HBM-PIM command backend, with every
    number the bank model reads stated."""
    return {**DPU, "backend": "hbmpim_cmd", "hbm_lanes": 16,
            "hbm_crf_slots": 64, "mram_bw_scale": 1.0,
            "coalesced_bw_mult": 3.4, **over}


def _crf(commands):
    """A bank-model command stream as the program's CRF program."""
    from repro.core import hbmpim
    code = {"bank": hbmpim.bank, "grf_a": hbmpim.grf_a,
            "grf_b": hbmpim.grf_b, "srf": hbmpim.srf}
    p = hbmpim.CrfProgram()
    for op, *ops in commands:
        if op == "JUMP":
            p.jump(*ops)
        elif op == "EXIT":
            p.exit_()
        elif op == "NOP":
            p.nop()
        else:
            getattr(p, op.lower())(*(code[kind](i) for kind, i in ops))
    return p


def test_hbm_bank_model_by_hand():
    from repro.core import hbmpim
    from repro.core.config import DPUConfig
    from repro.core.host import PIMSystem
    dpu = _hbm_dpu(n_dpus=1, mram_bytes=4096)
    hand = [
        # 1 + a row miss (tRP + tRCD + tCL = 48 DRAM cycles = 14) + the
        # burst (64 bytes at 2 x 3.4 bytes a cycle = 10): 25
        ("MOV", ("grf_a", 0), ("bank", 3)),
        # 1 + the destination on the open row (tCL = 16 = 5) + 10: 16
        ("ADD", ("bank", 3), ("grf_a", 0), ("srf", 1)),
        # 1 + a on the open row (5 + 10) + a row miss for row 4 (14 +
        # 10): 40
        ("MAC", ("bank", 4), ("bank", 3), ("srf", 1)),
        ("NOP",),
        ("JUMP", 3, 2),          # NOP and JUMP three times each: 6
        ("EXIT",),               # 1
    ]
    bank = list(range(100, 100 + 1024))
    srf = [0, 7, 0, 0, 0, 0, 0, 0]
    got = list(bank)
    # 88 cycles; 16 issued for each of the three compute commands, one
    # for each of the seven others
    assert hbm_bank.run(hand, list(srf), got, dpu=dpu) == (88, 55)
    row3 = [v + 7 for v in bank[48:64]]
    assert got[48:64] == row3
    assert got[64:80] == [z + 7 * x for z, x in zip(bank[64:80], row3)]
    assert got[80:] == bank[80:] and got[:48] == bank[:48]
    # the program's command engine agrees, cycle for cycle and word for word
    system = PIMSystem(DPUConfig(**dpu))
    st, _ = hbmpim.launch_commands(system, "hand", _crf(hand),
                                   np.array([bank], np.int32),
                                   np.array(srf, np.int32))
    assert (int(st["cycle"][0]), int(st["c_issued"][0])) == (88, 55)
    assert st["mram"][0].tolist() == got


def _tiny_gemvs(**over) -> spec.Cell:
    """GEMVS in all-bank mode on 4 banks; 65 CRF slots split each chunk
    of 9 groups of 16 rows into launches of 8 groups and of 1."""
    return spec.Cell(
        name="tiny.gemvs-hbm", chips=1,
        config={"dpu": _hbm_dpu(n_dpus=4, hbm_crf_slots=65, **over),
                "timing_model": "hbm_bank"},
        traffic={"workload": "GEMVS", "scale": 0.075, "mram_bytes": 1 << 16,
                 "seed_pool": 1, "sizes": {"rows": 144}},
        end_to_end=[], per_layer=[])


def test_hbm_bank_model_matches_the_program_on_every_bank():
    """A tiny GEMVS in all-bank mode: every launch's cycles and issued
    commands on every bank, and the words the last launch leaves."""
    cell = _tiny_gemvs()
    sim = harness.Sim(data_seed=3)
    harness.Program(cell).simulate(sim)
    assert sim.error is None, sim.error
    dpu, sizes = cell.dpu(), cell.traffic["sizes"]
    want = cell.reference().launches(dpu, sizes, 3)
    # two launches per chunk of eight columns
    assert len(want) == len(sim.launches) == 16
    for launch, got in zip(want, sim.launches):
        for k in range(4):
            assert hbm_bank.time_launch(dpu, "GEMVS", launch, k) == \
                (int(got.cycles[k]), int(got.issued[k]))
    cmds, srf, _ = want[-1]
    bank = hbm_bank.bank_words(dpu, want[-1], 2)
    hbm_bank.run(cmds, srf[2].tolist(), bank, dpu=dpu)
    assert bank[:sim.image.shape[1]].tolist() == sim.image[2].tolist()


@pytest.mark.parametrize("backend, workload, warmup", [
    ("", "VA", "kernel"), ("scalar", "BFS", "kernel"),
    ("hbmpim", "VA", "kernel"), ("hbmpim", "GEMVS", "crf"),
    ("hbmpim_cmd", "GEMVS", "crf")])
def test_warmup_follows_the_backend(backend, workload, warmup):
    cell = spec.load_cell("rank64.va")
    cell.config = {**cell.config,
                   "dpu": {**cell.config["dpu"], "backend": backend}}
    cell.traffic = {**cell.traffic, "workload": workload}
    assert Path(cell.warmup().__file__).stem == warmup


@pytest.mark.parametrize("backend", ["hbmpim", "hbmpim_cmd"])
def test_crf_warmup_builds_the_keys_the_launches_use(backend, monkeypatch):
    """The command-engine executables that the warm-up builds are those
    that the simulation's launches then look up, and no others."""
    from repro.core import compile_cache
    used, inner = [], compile_cache._get_entry

    def get_entry(*args):
        entry, hit = inner(*args)
        used.append(entry.key)
        return entry, hit

    monkeypatch.setattr(compile_cache, "_ENTRIES", {})
    monkeypatch.setattr(compile_cache, "_get_entry", get_entry)
    prog = harness.Program(_tiny_gemvs(backend=backend))
    warmed = prog.prewarm()
    assert len(set(warmed)) == 2, "one per program length"
    del used[:]
    misses = compile_cache.stats()["misses"]
    sim = harness.Sim(data_seed=1)
    prog.simulate(sim)
    assert sim.error is None, sim.error
    assert len(used) == 16 and set(used) == set(warmed)
    assert compile_cache.stats()["misses"] == misses
