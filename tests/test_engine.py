"""Cycle-level engine: semantics + microarchitectural timing properties."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import engine
from repro.core.asm import Program, Reg, TID, ZERO
from repro.core.config import DPUConfig
from repro.core.isa import Op


def run_prog(p, cfg=None, n_threads=1, args=(), mram=None):
    cfg = cfg or DPUConfig(n_dpus=1, n_tasklets=n_threads,
                           mram_bytes=1 << 14)
    binary = p.binary(cfg.iram_instrs)
    wram = np.zeros((cfg.n_dpus, 16), np.int32)
    for i, a in enumerate(args):
        wram[:, i] = a
    if mram is None:
        mram = np.zeros((cfg.n_dpus, cfg.mram_words), np.int32)
    return engine.run(cfg, binary, wram, mram, n_threads=n_threads)


# ---------------------------------------------------------------------------
# functional semantics (hypothesis: random ALU programs vs python oracle)
# ---------------------------------------------------------------------------

_ALU_OPS = [Op.ADD, Op.SUB, Op.AND, Op.OR, Op.XOR, Op.SLL, Op.SRL, Op.SRA,
            Op.MUL, Op.DIV, Op.SLT, Op.SLTU]


def _py_alu(op, a, b):
    a32 = np.int32(a)
    b32 = np.int32(b)
    sh = np.uint32(b32) & 31
    with np.errstate(over="ignore"):
        if op == Op.ADD:
            return np.int32(a32 + b32)
        if op == Op.SUB:
            return np.int32(a32 - b32)
        if op == Op.AND:
            return np.int32(a32 & b32)
        if op == Op.OR:
            return np.int32(a32 | b32)
        if op == Op.XOR:
            return np.int32(a32 ^ b32)
        if op == Op.SLL:
            return np.int32(np.uint32(a32) << sh)
        if op == Op.SRL:
            return np.int32(np.uint32(a32) >> sh)
        if op == Op.SRA:
            return np.int32(a32 >> np.int32(sh))
        if op == Op.MUL:
            return np.int32(np.int64(a32) * np.int64(b32) & 0xFFFFFFFF)
        if op == Op.DIV:
            if b32 == 0:
                return np.int32(-1)
            return np.int32(np.fix(np.int64(a32) / np.int64(b32)))
        if op == Op.SLT:
            return np.int32(a32 < b32)
        if op == Op.SLTU:
            return np.int32(np.uint32(a32) < np.uint32(b32))
    raise AssertionError(op)


@settings(max_examples=20, deadline=None)
@given(st.lists(
    st.tuples(st.sampled_from(_ALU_OPS),
              st.integers(-2**31, 2**31 - 1),
              st.integers(-2**31, 2**31 - 1)),
    min_size=1, max_size=8))
def test_alu_program_matches_oracle(ops):
    p = Program("h", 1)
    ra, rb, rd = p.regs("a", "b", "d")
    want = []
    for i, (op, a, b) in enumerate(ops):
        p.li(ra, a)
        p.li(rb, b)
        p._emit(op, rd, ra, rb)
        p.sw(ZERO, 64 + 4 * i, rd)
        want.append(_py_alu(op, a, b))
    p.stop()
    st_ = run_prog(p)
    got = st_["wram"][0, 16:16 + len(ops)]
    assert list(got) == [int(w) for w in want], (ops, list(got), want)


# ---------------------------------------------------------------------------
# timing properties
# ---------------------------------------------------------------------------


def _chain_prog(n_instr=20):
    p = Program("chain", 1)
    r = p.reg("r")
    for _ in range(n_instr):
        p.add(r, r, 1)
    p.stop()
    return p, n_instr


def test_revolver_min_issue_distance():
    """One thread, dependent chain: cycles ~= n * revolver_cycles."""
    p, n = _chain_prog()
    st_ = run_prog(p)
    cycles = int(st_["cycle"][0])
    assert cycles >= n * 11, cycles


def test_forwarding_collapses_chain():
    p, n = _chain_prog()
    cfg = DPUConfig(n_dpus=1, n_tasklets=1, mram_bytes=1 << 14,
                    forwarding=True)
    st_ = run_prog(p, cfg=cfg)
    assert int(st_["cycle"][0]) <= 2 * n + 10


def test_rf_parity_hazard_counted():
    """Same-parity dual-read (r0, r2) stalls the port; unified RF removes it."""
    def prog():
        p = Program("rf", 2)
        a = p.reg("a")   # r0
        _ = p.reg("pad")  # r1
        b = p.reg("b")   # r2
        for _ in range(30):
            p.add(a, a, b)  # reads r0 & r2 -> even/even conflict
        p.stop()
        return p

    st_base = run_prog(prog(), cfg=DPUConfig(n_dpus=1, n_tasklets=2,
                                             mram_bytes=1 << 14), n_threads=2)
    st_uni = run_prog(prog(), cfg=DPUConfig(n_dpus=1, n_tasklets=2,
                                            mram_bytes=1 << 14,
                                            unified_rf=True), n_threads=2)
    assert int(st_base["c_idle_rf"][0]) > 0
    assert int(st_uni["c_idle_rf"][0]) == 0
    assert int(st_uni["cycle"][0]) <= int(st_base["cycle"][0])


def test_superscalar_dualissue():
    """Two independent threads: 2-way issue ~halves the runtime."""
    def prog():
        p = Program("ss", 2)
        r = p.reg("r")
        for _ in range(64):
            p.add(r, r, 1)
        p.stop()
        return p

    cfg1 = DPUConfig(n_dpus=1, n_tasklets=2, mram_bytes=1 << 14,
                     forwarding=True, unified_rf=True)
    cfg2 = cfg1.replace(superscalar=2)
    c1 = int(run_prog(prog(), cfg=cfg1, n_threads=2)["cycle"][0])
    c2 = int(run_prog(prog(), cfg=cfg2, n_threads=2)["cycle"][0])
    assert c2 < 0.7 * c1, (c1, c2)


def test_event_skip_equivalence():
    """Fast-forwarding must not change results or cycle counts."""
    p = Program("skip", 2)
    buf = p.walloc("buf", 64)
    w, m = p.regs("w", "m")
    p.li(w, buf)
    p.li(m, 128)
    for _ in range(4):
        p.ldma(w, m, 64)
        p.sdma(w, m, 64)
    p.barrier()
    p.stop()

    outs = []
    for skip in (False, True):
        cfg = DPUConfig(n_dpus=2, n_tasklets=2, mram_bytes=1 << 14,
                        event_skip=skip)
        binary = p.binary(cfg.iram_instrs)
        mram = np.arange(2 * cfg.mram_words, dtype=np.int32).reshape(2, -1)
        st_ = engine.run(cfg, binary, np.zeros((2, 16), np.int32), mram,
                         n_threads=2)
        outs.append(st_)
    a, b = outs
    assert np.array_equal(a["cycle"], b["cycle"])
    assert np.array_equal(a["wram"], b["wram"])
    assert int(a["c_idle_mem"].sum()) == int(b["c_idle_mem"].sum())


def test_mutex_mutual_exclusion():
    """N threads increment a shared counter under a mutex; result exact."""
    nt = 8
    p = Program("mutex", nt)
    cnt = p.walloc("cnt", 8)
    v, i = p.regs("v", "i")
    with p.for_range(i, 0, 10):
        p.acquire(0)
        p.lw(v, ZERO, cnt)
        p.add(v, v, 1)
        p.sw(ZERO, cnt, v)
        p.release(0)
    p.stop()
    st_ = run_prog(p, cfg=DPUConfig(n_dpus=1, n_tasklets=nt,
                                    mram_bytes=1 << 14), n_threads=nt)
    assert int(st_["wram"][0, cnt // 4]) == nt * 10
    assert int(st_["c_acq_retry"][0]) > 0  # contention happened


def test_barrier_rendezvous():
    """Thread 0 writes, everyone reads after barrier."""
    nt = 4
    p = Program("bar", nt)
    flag = p.walloc("flag", 8)
    out = p.walloc("out", 4 * nt)
    v, addr = p.regs("v", "addr")
    sk = p.newlabel("sk")
    p.bne(TID, ZERO, sk)
    p.li(v, 1234)
    p.sw(ZERO, flag, v)
    p.label(sk)
    p.barrier()
    p.lw(v, ZERO, flag)
    p.sll(addr, TID, 2)
    p.add(addr, addr, out)
    p.sw(addr, 0, v)
    p.stop()
    st_ = run_prog(p, cfg=DPUConfig(n_dpus=1, n_tasklets=nt,
                                    mram_bytes=1 << 14), n_threads=nt)
    assert list(st_["wram"][0, out // 4: out // 4 + nt]) == [1234] * nt


def test_frfcfs_row_hit_priority():
    """Requests to the open row are served first (row-hit count high when
    threads stream the same region)."""
    nt = 4
    p = Program("fr", nt)
    buf = p.walloc("buf", nt * 64)
    w, m, i = p.regs("w", "m", "i")
    p.mul(w, TID, 64)
    p.add(w, w, buf)
    p.mul(m, TID, 64)          # all threads inside one 1 KB row
    with p.for_range(i, 0, 8):
        p.ldma(w, m, 64)
        p.add(m, m, 256)       # stay within rows mostly
    p.stop()
    st_ = run_prog(p, cfg=DPUConfig(n_dpus=1, n_tasklets=nt,
                                    mram_bytes=1 << 16), n_threads=nt)
    assert int(st_["c_row_hit"][0]) > int(st_["c_row_miss"][0])


def test_dma_size_dynamic_register():
    p = Program("dyn", 1)
    buf = p.walloc("buf", 64)
    w, m, sz = p.regs("w", "m", "sz")
    p.li(w, buf)
    p.li(m, 256)
    p.li(sz, 32)
    p.ldma(w, m, sz)
    p.stop()
    cfg = DPUConfig(n_dpus=1, n_tasklets=1, mram_bytes=1 << 14)
    binary = p.binary(cfg.iram_instrs)
    mram = np.arange(cfg.mram_words, dtype=np.int32)[None]
    st_ = engine.run(cfg, binary, np.zeros((1, 16), np.int32), mram,
                     n_threads=1)
    assert list(st_["wram"][0, buf // 4: buf // 4 + 8]) == list(range(64, 72))


def test_counters_partition_cycles():
    p, _ = _chain_prog(30)
    st_ = run_prog(p)
    total = (int(st_["c_active"][0]) + int(st_["c_idle_mem"][0])
             + int(st_["c_idle_rev"][0]) + int(st_["c_idle_rf"][0]))
    assert total == int(st_["cycle"][0])
