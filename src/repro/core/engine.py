"""Vectorized cycle-level DPU engine.

The paper's event/loop C++ simulator is re-thought for TPU execution:
*all* microarchitectural state is a pytree of int32 arrays
with a leading DPU axis; one simulated cycle is a pure function
``SimState -> SimState`` driven by ``jax.lax.while_loop``; every DPU in the
system advances in the same vectorized step (lane-per-DPU).

Modeled faithfully (paper §II-A, Table I):
  * in-order 14-stage pipeline, max IPC 1 (issue-port model);
  * revolver scheduling — >= 11 cycles between issues of the same tasklet;
  * odd/even register-file structural hazard (same-parity dual reads
    occupy the issue port for an extra cycle);
  * WRAM loads/stores 1 cycle; MRAM reachable only via blocking DMA;
  * per-bank FR-FCFS DRAM with row-buffer + DDR4-2400 timing;
  * busy-wait ACQUIRE (sync-instruction waste, Fig. 9), hardware BARRIER.

Case-study features are config flags: forwarding (D), unified RF (R),
2-way superscalar (S), frequency (F), MMU/TLB, cache-centric mode.

Beyond-paper: ``event_skip`` fast-forwards idle gaps to the next event
(issue-eligibility or DMA completion) while attributing every skipped
cycle to the paper's idle taxonomy — a pure-performance change validated
bit-exact against the cycle-by-cycle mode
(``tests/test_engine.py::test_event_skip_equivalence``).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isa
from repro.core.config import DPUConfig
from repro.core.isa import Op

# thread status
RUN, BLK_DMA, BLK_BAR, DONE = 0, 1, 2, 3
INF = jnp.int32(1 << 30)
MAX_DMA_BYTES = 2048  # UPMEM DMA transfer limit
#: widest per-lane slice (entries per DPU) read or written as a dense
#: one-hot select.  XLA lowers a scatter with dynamic indices on the TPU
#: to a serial loop over the updated lanes, at about the same cost
#: whatever the array's size, while a select over a few hundred entries
#: per lane is one fused vector op.  Wider slices (WRAM and MRAM words,
#: see :func:`to_carry`) keep their gathers and scatters.
ONEHOT_MAX = 1024


# ---------------------------------------------------------------------------
# Per-lane state access
# ---------------------------------------------------------------------------


def _lane_mask(x, idx):
    """Mask over axes ``1..len(idx)`` of ``x``, true per DPU lane ``d``
    at ``idx[0][d], idx[1][d], ...``; size one on any trailing axis."""
    mask = True
    for k, i in enumerate(idx):
        shape = [1] * x.ndim
        shape[1 + k] = x.shape[1 + k]
        mask = mask & (jnp.arange(x.shape[1 + k]).reshape(shape)
                       == i.reshape((-1,) + (1,) * (x.ndim - 1)))
    return mask


def _narrow(x, idx):
    return int(np.prod(x.shape[1:1 + len(idx)])) <= ONEHOT_MAX


def lane_get(x, *idx):
    """``x[d, idx[0][d], ...]`` for every DPU lane ``d`` of int or bool
    ``x``: a one-hot pick over a narrow slice, a gather over a wide one.
    An index out of range reads 0 (False) from a narrow slice."""
    if not _narrow(x, idx):
        return x[(jnp.arange(x.shape[0]),) + idx]
    axes = tuple(range(1, 1 + len(idx)))
    mask = _lane_mask(x, idx)
    if x.dtype == jnp.bool_:
        return (mask & x).any(axes)
    return jnp.where(mask, x, 0).sum(axes)


def lane_set(x, idx, v, when):
    """``x`` with ``x[d, idx[0][d], ...] = v[d]`` on the lanes where
    ``when`` holds: a one-hot select over a narrow slice, a scatter into a
    wide one.  ``idx`` is one index array or a tuple of them; an index out
    of range writes nothing."""
    idx = idx if isinstance(idx, tuple) else (idx,)
    v = jnp.asarray(v)
    if not _narrow(x, idx):
        at = (jnp.arange(x.shape[0]),) + idx
        return x.at[at].set(jnp.where(when, v, x[at]))
    mask = _lane_mask(x, idx) & when.reshape((-1,) + (1,) * (x.ndim - 1))
    if v.ndim:
        v = v.reshape(v.shape[:1] + (1,) * len(idx) + v.shape[1:])
    return jnp.where(mask, v, x)


def lane_add(x, idx, v):
    """``x`` with ``v[d]`` added at ``x[d, idx[d]]`` on every lane (the
    counters it updates are all narrow)."""
    return x + jnp.where(_lane_mask(x, (idx,)), v[:, None], 0)


# ---------------------------------------------------------------------------
# ALU datapath (pure jnp)
# ---------------------------------------------------------------------------


def select_tree(op, results, lo=0, hi=None):
    """Balanced binary ``jnp.where`` tree dispatching ``op`` over
    ``results[lo:hi]`` (result ``i`` for ``op == lo + i``).

    Replaces a flat N-way ``jnp.select`` chain: log2(N) select depth
    instead of N predicates + an N-deep select, which lowers to a much
    smaller XLA graph in the per-cycle hot loop.  Out-of-range ``op``
    clamps to the nearest end — callers mask those lanes."""
    if hi is None:
        hi = lo + len(results)
    assert len(results) == hi - lo
    if hi - lo == 1:
        return results[0]
    mid = (lo + hi) // 2
    return jnp.where(op < mid,
                     select_tree(op, results[:mid - lo], lo, mid),
                     select_tree(op, results[mid - lo:], mid, hi))


def alu_exec(op, a, b):
    """Vectorized 12-way ALU.  op/a/b: int32 arrays of equal shape.

    Lanes whose ``op`` is outside [0, 12) (non-ALU opcodes) produce an
    arbitrary value; the engine masks the result on ``op <= Op.SLTU``."""
    sh = b.astype(jnp.uint32) & 31
    au = a.astype(jnp.uint32)
    bu = b.astype(jnp.uint32)
    safe_b = jnp.where(b == 0, 1, b)
    results = [
        a + b,
        a - b,
        a & b,
        a | b,
        a ^ b,
        (au << sh).astype(jnp.int32),
        (au >> sh).astype(jnp.int32),
        a >> sh.astype(jnp.int32),
        a * b,
        jnp.where(b == 0, -1, jax.lax.div(a, safe_b)),
        (a < b).astype(jnp.int32),
        (au < bu).astype(jnp.int32),
    ]
    return select_tree(op, results)


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------


def make_state_np(cfg: DPUConfig, binary: isa.Binary, wram_init, mram_init,
                  n_threads: int = None) -> Dict:
    """Initial microarchitectural state as a host-numpy pytree (the
    compile cache pads/masks this before device placement;
    :func:`make_state` is the device-array convenience wrapper)."""
    D = cfg.n_dpus
    T = n_threads or cfg.n_tasklets
    W = cfg.wram_words
    M = mram_init.shape[1]
    regs = np.zeros((D, T, isa.N_REGS), np.int32)
    regs[:, :, isa.R_DPU] = np.arange(D)[:, None]
    regs[:, :, isa.R_NDPU] = D
    regs[:, :, isa.R_TID] = np.arange(T)[None, :]
    regs[:, :, isa.R_NT] = T

    wram = np.zeros((D, W), np.int32)
    wram[:, : wram_init.shape[1]] = wram_init

    n_sets = max(1, cfg.dcache_bytes // cfg.line_bytes // cfg.dcache_ways)
    ways = cfg.dcache_ways if cfg.cache_mode else 1
    sets = n_sets if cfg.cache_mode else 1

    st = {
        "cycle": np.zeros(D, np.int32),
        "pc": np.zeros((D, T), np.int32),
        "regs": regs,
        "status": np.full((D, T), RUN, np.int32),
        "next_issue": np.zeros((D, T), np.int32),
        "last_dest": np.full((D, T), -1, np.int32),
        "last_ready": np.zeros((D, T), np.int32),
        "port_busy": np.zeros(D, np.int32),
        "rr": np.zeros(D, np.int32),
        "wram": wram,
        "mram": mram_init.astype(np.int32),
        "atomic": np.zeros((D, cfg.atomic_bits), np.int32),
        # DMA request latches (one per thread)
        "req_valid": np.zeros((D, T), bool),
        "req_wram": np.zeros((D, T), np.int32),
        "req_mram": np.zeros((D, T), np.int32),
        "req_bytes": np.zeros((D, T), np.int32),
        "req_write": np.zeros((D, T), bool),
        "req_enq": np.zeros((D, T), np.int32),
        # DRAM engine
        "eng_active": np.zeros(D, bool),
        "eng_thread": np.zeros(D, np.int32),
        "eng_finish": np.zeros(D, np.int32),
        "open_row": np.full(D, -1, np.int32),
        # MMU
        "tlb_tags": np.full((D, cfg.tlb_entries), -1, np.int32),
        "tlb_lru": np.zeros((D, cfg.tlb_entries), np.int32),
        # D$ (cache mode)
        "dc_tags": np.full((D, sets, ways), -1, np.int32),
        "dc_lru": np.zeros((D, sets, ways), np.int32),
        "dc_dirty": np.zeros((D, sets, ways), bool),
        # counters
        "c_active": np.zeros(D, np.int32),
        "c_idle_mem": np.zeros(D, np.int32),
        "c_idle_rev": np.zeros(D, np.int32),
        "c_idle_rf": np.zeros(D, np.int32),
        "c_issued": np.zeros(D, np.int32),
        "c_cls": np.zeros((D, 6), np.int32),
        "c_hist": np.zeros((D, T + 1), np.int32),
        "c_dma_rd": np.zeros(D, np.int32),
        "c_dma_wr": np.zeros(D, np.int32),
        "c_dma_rd_bytes": np.zeros(D, np.float32),
        "c_dma_wr_bytes": np.zeros(D, np.float32),
        "c_row_hit": np.zeros(D, np.int32),
        "c_row_miss": np.zeros(D, np.int32),
        "c_tlb_hit": np.zeros(D, np.int32),
        "c_tlb_miss": np.zeros(D, np.int32),
        "c_dc_hit": np.zeros(D, np.int32),
        "c_dc_miss": np.zeros(D, np.int32),
        "c_acq_retry": np.zeros(D, np.int32),
        # TLP time series
        "ts_buf": np.zeros((D, cfg.timeseries_len), np.float32),
        "ts_acc": np.zeros(D, np.float32),
    }
    return st


def make_state(cfg: DPUConfig, binary: isa.Binary, wram_init, mram_init,
               n_threads: int = None) -> Dict:
    return jax.tree_util.tree_map(
        jnp.asarray, make_state_np(cfg, binary, wram_init, mram_init,
                                   n_threads))


#: WRAM words (over all lanes) up to which the scalar step keeps WRAM and
#: MRAM as ``[D, W]`` / ``[D, M]`` in its loop carry, and above which it
#: keeps them flat.  On a TPU v5e, XLA scatters one word per lane into a
#: tiled ``[D, W]`` int32 array in place up to 32 MiB (512 lanes of 64 KB
#: WRAM), and above that copies the whole array to a linear layout and
#: back around every WRAM store; a flat carry has that layout throughout.
#: Below it the 2-D store is the faster one (measured at 64 lanes).
FLAT_CARRY_WORDS = 1 << 23


def _lane_words(x, D, idx):
    """Index of word ``idx[d]`` (or ``idx[d, k]``) of lane ``d`` into lane
    memory ``x``, ``[D, W]`` or flat ``[D * W]``."""
    dd = jnp.arange(D).reshape((D,) + (1,) * (idx.ndim - 1))
    if x.ndim == 2:
        return dd, idx
    return dd * (x.shape[0] // D) + idx


def to_carry(st: Dict) -> Dict:
    """The state as the scalar step's loop carries it: ``st`` itself, or,
    where WRAM holds more than :data:`FLAT_CARRY_WORDS` words, ``st``
    with ``wram`` and ``mram`` flattened to ``[D * W]`` and ``[D * M]``
    (numpy or jax arrays; a C-contiguous numpy reshape is a view)."""
    if st["wram"].size <= FLAT_CARRY_WORDS:
        return st
    D = st["status"].shape[0]
    words = D * max(st["wram"].shape[1], st["mram"].shape[1])
    if words >= 1 << 31:
        raise ValueError(f"{words} words of WRAM or MRAM over {D} DPUs: "
                         "the flat int32 word index stops at 2**31")
    return dict(st, wram=st["wram"].reshape(-1), mram=st["mram"].reshape(-1))


def from_carry(st: Dict) -> Dict:
    """Inverse of :func:`to_carry`: ``wram`` and ``mram`` as ``[D, W]``
    and ``[D, M]``."""
    D = st["status"].shape[0]
    return dict(st, wram=st["wram"].reshape(D, -1),
                mram=st["mram"].reshape(D, -1))


# ---------------------------------------------------------------------------
# One issue slot
# ---------------------------------------------------------------------------


def _issue_one(cfg: DPUConfig, ir, st, cycle, running, already, slot_block):
    """Try to issue one instruction per DPU.  Returns (st, issued, hazard,
    cls_onehot_updates already applied).  ``st`` is in the carry form of
    :func:`to_carry`: WRAM and MRAM as ``[D, W]`` and ``[D, M]`` or flat,
    indexed through :func:`_lane_words`."""
    D, T = st["status"].shape
    W = st["wram"].size // D
    M = st["mram"].size // D
    iop, ird, ira, irb, iimm, iui = ir

    ready = (st["status"] == RUN) & (st["next_issue"] <= cycle[:, None])
    if already is not None:
        ready = ready & ~already  # superscalar: a thread dual-issuing is not allowed
    can = running & (st["port_busy"] == 0) & ready.any(-1) & ~slot_block

    prio = (jnp.arange(T)[None, :] - st["rr"][:, None]) % T
    tsel = jnp.argmin(jnp.where(ready, prio, INF), axis=-1)
    valid = can

    pcv = lane_get(st["pc"], tsel)
    op = iop[pcv]
    rdv = ird[pcv]
    rav = ira[pcv]
    rbv = irb[pcv]
    immv = iimm[pcv]
    uiv = iui[pcv] != 0

    regs_t = lane_get(st["regs"], tsel)                 # (D, N_REGS)
    a = lane_get(regs_t, rav)
    breg = lane_get(regs_t, rbv)
    b = jnp.where(uiv, immv, breg)

    # ---- datapath ----
    alu = alu_exec(op, a, b)
    addr = a + immv
    widx = jnp.clip(addr >> 2, 0, W - 1)
    wat = _lane_words(st["wram"], D, widx)
    ldval = st["wram"][wat]
    special = jnp.stack(
        [regs_t[:, isa.R_TID], regs_t[:, isa.R_NT],
         regs_t[:, isa.R_DPU], regs_t[:, isa.R_NDPU]], -1)
    spc = lane_get(special, jnp.clip(immv, 0, 3))

    res = jnp.where(op <= Op.SLTU, alu,
          jnp.where(op == Op.LW, ldval,
          jnp.where(op == Op.JAL, pcv + 1, spc)))

    writes_rd = jnp.asarray(isa.WRITES_RD)[op] & valid
    regs = lane_set(st["regs"], (tsel, rdv), res, writes_rd)

    # ---- stores ----
    do_sw = valid & (op == Op.SW)
    wram = st["wram"].at[wat].set(jnp.where(do_sw, breg, ldval))

    # ---- cache-centric mode: LW/SW go through the D$ timing model ----
    status = st["status"]
    next_issue = st["next_issue"]
    req_valid, req_wram, req_mram = st["req_valid"], st["req_wram"], st["req_mram"]
    req_bytes, req_write, req_enq = st["req_bytes"], st["req_write"], st["req_enq"]
    dc_tags, dc_lru, dc_dirty = st["dc_tags"], st["dc_lru"], st["dc_dirty"]
    c_dc_hit, c_dc_miss = st["c_dc_hit"], st["c_dc_miss"]
    if cfg.cache_mode:
        is_mem = valid & ((op == Op.LW) | (op == Op.SW))
        line = addr // cfg.line_bytes
        n_sets = dc_tags.shape[1]
        cset = jnp.where(is_mem, line % n_sets, 0)
        tags_s = lane_get(dc_tags, cset)                # (D, ways)
        match = tags_s == line[:, None]
        hit = is_mem & match.any(-1)
        miss = is_mem & ~match.any(-1)
        hitway = jnp.argmax(match, -1)
        victim = jnp.argmin(lane_get(dc_lru, cset), -1)
        way = jnp.where(hit, hitway, victim)
        # dirty-victim writeback folded into the fill size
        vic_dirty = (lane_get(dc_dirty, cset, victim)
                     & (lane_get(tags_s, victim) >= 0))
        fill_bytes = cfg.line_bytes + jnp.where(vic_dirty, cfg.line_bytes, 0)
        # install on miss (data is functionally in WRAM already)
        dc_tags = lane_set(dc_tags, (cset, way), line, is_mem)
        dc_lru = lane_set(dc_lru, (cset, way), cycle, is_mem)
        new_dirty = jnp.where(miss, op == Op.SW,
                              lane_get(dc_dirty, cset, way) | (op == Op.SW))
        dc_dirty = lane_set(dc_dirty, (cset, way), new_dirty, is_mem)
        # miss blocks the tasklet behind a DRAM fill of the line
        status = lane_set(status, tsel, BLK_DMA, miss)
        req_valid = lane_set(req_valid, tsel, True, miss)
        req_mram = lane_set(req_mram, tsel, line * cfg.line_bytes, miss)
        req_bytes = lane_set(req_bytes, tsel, fill_bytes, miss)
        req_write = lane_set(req_write, tsel, False, miss)
        req_enq = lane_set(req_enq, tsel, cycle, miss)
        c_dc_hit = c_dc_hit + hit.astype(jnp.int32)
        c_dc_miss = c_dc_miss + miss.astype(jnp.int32)

    # ---- atomics ----
    mid = jnp.clip(immv, 0, st["atomic"].shape[1] - 1)
    held = lane_get(st["atomic"], mid) != 0
    acq_ok = valid & (op == Op.ACQUIRE) & ~held
    acq_retry = valid & (op == Op.ACQUIRE) & held
    rel = valid & (op == Op.RELEASE)
    atomic = lane_set(st["atomic"], mid, acq_ok.astype(jnp.int32),
                      acq_ok | rel)

    # ---- DMA ----
    do_dma = valid & ((op == Op.LDMA) | (op == Op.SDMA))
    if cfg.cache_mode:
        do_dma = do_dma & False  # cache-mode programs address memory directly
    size = jnp.where(uiv, immv, lane_get(regs_t, rdv))
    size = jnp.clip(size, 0, MAX_DMA_BYTES)
    is_w = op == Op.SDMA
    status = lane_set(status, tsel, BLK_DMA, do_dma)
    req_valid = lane_set(req_valid, tsel, True, do_dma)
    req_wram = lane_set(req_wram, tsel, a, do_dma)
    req_mram = lane_set(req_mram, tsel, breg, do_dma)
    req_bytes = lane_set(req_bytes, tsel, size, do_dma)
    req_write = lane_set(req_write, tsel, is_w, do_dma)
    req_enq = lane_set(req_enq, tsel, cycle, do_dma)

    # functional copy now (timing handled by the DRAM engine); data-race-free
    # programs observe identical results.  Two-tier widths: most DMAs are
    # small (BS probes 64 B, SpMV row pointers 8 B), so a narrow fast path
    # avoids the full 512-word gather/scatter (§Perf engine iteration 4).
    def mk_copy(nw):
        def do_copy(wm):
            wram_, mram_ = wm
            k = jnp.arange(nw)
            wbase = (jnp.where(do_dma, a, 0) >> 2)[:, None] + k[None, :]
            mbase = (jnp.where(do_dma, breg, 0) >> 2)[:, None] + k[None, :]
            nwords = (jnp.where(do_dma, size, 0) + 3) >> 2
            mask = (k[None, :] < nwords[:, None])
            wat_ = _lane_words(wram_, D, jnp.clip(wbase, 0, W - 1))
            mat_ = _lane_words(mram_, D, jnp.clip(mbase, 0, M - 1))
            rd_m = mram_[mat_]
            rd_w = wram_[wat_]
            ld_mask = mask & ~is_w[:, None] & do_dma[:, None]
            st_mask = mask & is_w[:, None] & do_dma[:, None]
            wram_ = wram_.at[wat_].set(jnp.where(ld_mask, rd_m, rd_w))
            mram_ = mram_.at[mat_].set(jnp.where(st_mask, rd_w, rd_m))
            return wram_, mram_
        return do_copy

    small = cfg.small_dma_words
    max_words = (jnp.where(do_dma, size, 0).max() + 3) >> 2

    def dispatch(wm):
        return jax.lax.cond(max_words <= small, mk_copy(small),
                            mk_copy(MAX_DMA_BYTES // 4), wm)

    wram, mram = jax.lax.cond(do_dma.any(), dispatch, lambda wm: wm,
                              (wram, st["mram"]))

    # ---- control flow ----
    eq = a == b
    lt = a < b
    ltu = a.astype(jnp.uint32) < b.astype(jnp.uint32)
    taken = jnp.select(
        [op == Op.BEQ, op == Op.BNE, op == Op.BLT, op == Op.BGE,
         op == Op.BLTU, op == Op.BGEU],
        [eq, ~eq, lt, ~lt, ltu, ~ltu], False)
    new_pc = jnp.where((op >= Op.BEQ) & (op <= Op.BGEU),
                       jnp.where(taken, immv, pcv + 1),
            jnp.where((op == Op.JUMP) | (op == Op.JAL), immv,
            jnp.where(op == Op.JR, a,
            jnp.where(acq_retry | (op == Op.STOP), pcv, pcv + 1))))
    pc = lane_set(st["pc"], tsel, new_pc, valid)

    status = lane_set(status, tsel, DONE, valid & (op == Op.STOP))
    status = lane_set(status, tsel, BLK_BAR, valid & (op == Op.BARRIER))

    # ---- issue gap: revolver / forwarding / long ops ----
    if cfg.forwarding:
        ld = lane_get(st["last_dest"], tsel)
        reads_ra = jnp.asarray(isa.READS_RA)[op]
        reads_rb = jnp.asarray(isa.READS_RB)[op] & ~uiv
        raw = (ld >= 0) & ((reads_ra & (rav == ld)) | (reads_rb & (rbv == ld)))
        nxt = jnp.maximum(cycle + 1,
                          jnp.where(raw, lane_get(st["last_ready"], tsel), 0))
    else:
        nxt = cycle + cfg.revolver_cycles
    nxt = nxt + jnp.where(op == Op.MUL, cfg.mul_extra,
                jnp.where(op == Op.DIV, cfg.div_extra, 0))
    next_issue = lane_set(next_issue, tsel, nxt, valid)

    last_dest = lane_set(st["last_dest"], tsel,
                         jnp.where(writes_rd, rdv, -1), valid)
    ready_at = cycle + jnp.where(op == Op.LW, cfg.wram_load_latency, 1)
    last_ready = lane_set(st["last_ready"], tsel, ready_at, valid)

    # ---- odd/even RF structural hazard ----
    reads_two = (jnp.asarray(isa.READS_RA)[op] & jnp.asarray(isa.READS_RB)[op]
                 & ~uiv)
    hazard = valid & reads_two & ((rav % 2) == (rbv % 2)) & (not cfg.unified_rf)
    # +2: the end-of-cycle decrement eats one, leaving the port busy for
    # exactly the next cycle (the second same-parity RF read slot)
    port_busy = st["port_busy"] + 2 * hazard.astype(jnp.int32)

    rr = jnp.where(valid, (tsel + 1) % T, st["rr"])

    # ---- counters ----
    cls = jnp.asarray(isa.OP_CLASS_TABLE)[op]
    c_cls = lane_add(st["c_cls"], cls, valid.astype(jnp.int32))
    new_st = dict(st)
    new_st.update(
        regs=regs, wram=wram, mram=mram, atomic=atomic, pc=pc, status=status,
        next_issue=next_issue, last_dest=last_dest, last_ready=last_ready,
        port_busy=port_busy, rr=rr,
        req_valid=req_valid, req_wram=req_wram, req_mram=req_mram,
        req_bytes=req_bytes, req_write=req_write, req_enq=req_enq,
        dc_tags=dc_tags, dc_lru=dc_lru, dc_dirty=dc_dirty,
        c_dc_hit=c_dc_hit, c_dc_miss=c_dc_miss,
        c_issued=st["c_issued"] + valid.astype(jnp.int32),
        c_cls=c_cls,
        c_acq_retry=st["c_acq_retry"] + acq_retry.astype(jnp.int32),
        c_dma_rd=st["c_dma_rd"] + (do_dma & ~is_w).astype(jnp.int32),
        c_dma_wr=st["c_dma_wr"] + (do_dma & is_w).astype(jnp.int32),
        c_dma_rd_bytes=st["c_dma_rd_bytes"]
        + jnp.where(do_dma & ~is_w, size, 0).astype(jnp.float32),
        c_dma_wr_bytes=st["c_dma_wr_bytes"]
        + jnp.where(do_dma & is_w, size, 0).astype(jnp.float32),
    )
    issued_mask = lane_set(jnp.zeros_like(st["status"], bool), tsel, True,
                           valid)
    return new_st, valid, hazard, issued_mask


# ---------------------------------------------------------------------------
# DRAM engine (per-DPU bank, FR-FCFS)
# ---------------------------------------------------------------------------


def _dram_step(cfg: DPUConfig, st, cycle):
    D, T = st["status"].shape

    # completions
    comp = st["eng_active"] & (st["eng_finish"] <= cycle)
    tf = st["eng_thread"]
    status = lane_set(st["status"], tf, RUN, comp)
    next_issue = lane_set(st["next_issue"], tf, cycle + 1, comp)
    req_valid = lane_set(st["req_valid"], tf, False, comp)
    eng_active = st["eng_active"] & ~comp

    # FR-FCFS selection
    can = ~eng_active & req_valid.any(-1)
    row = st["req_mram"] // cfg.row_bytes
    hit = row == st["open_row"][:, None]
    score = jnp.where(req_valid, hit.astype(jnp.int32) * INF - st["req_enq"], -INF)
    j = jnp.argmax(score, -1)
    b_j = lane_get(st["req_bytes"], j)
    m_j = lane_get(st["req_mram"], j)
    hit_j = lane_get(hit, j)
    end_row = (m_j + jnp.maximum(b_j, 1) - 1) // cfg.row_bytes
    extra_rows = end_row - lane_get(row, j)
    overhead = jnp.where(hit_j, cfg.row_hit_overhead, cfg.row_miss_overhead)
    overhead = overhead + extra_rows * cfg.row_miss_overhead
    transfer = jnp.ceil(b_j / cfg.effective_mram_bw).astype(jnp.int32)

    tlb_tags, tlb_lru = st["tlb_tags"], st["tlb_lru"]
    c_tlb_hit, c_tlb_miss = st["c_tlb_hit"], st["c_tlb_miss"]
    mmu_pen = jnp.zeros(D, jnp.int32)
    if cfg.mmu:
        page = m_j // cfg.page_bytes
        match = tlb_tags == page[:, None]
        t_hit = match.any(-1)
        mmu_pen = jnp.where(t_hit, 0, cfg.row_miss_overhead)
        way = jnp.where(t_hit, jnp.argmax(match, -1), jnp.argmin(tlb_lru, -1))
        tlb_tags = lane_set(tlb_tags, way, page, can)
        tlb_lru = lane_set(tlb_lru, way, cycle, can)
        c_tlb_hit = c_tlb_hit + (can & t_hit).astype(jnp.int32)
        c_tlb_miss = c_tlb_miss + (can & ~t_hit).astype(jnp.int32)

    service = overhead + transfer + mmu_pen
    new = dict(st)
    new.update(
        status=status, next_issue=next_issue, req_valid=req_valid,
        eng_active=eng_active | can,
        eng_thread=jnp.where(can, j, st["eng_thread"]),
        eng_finish=jnp.where(can, cycle + service, st["eng_finish"]),
        open_row=jnp.where(can, end_row, st["open_row"]),
        tlb_tags=tlb_tags, tlb_lru=tlb_lru,
        c_tlb_hit=c_tlb_hit, c_tlb_miss=c_tlb_miss,
        c_row_hit=st["c_row_hit"] + (can & hit_j).astype(jnp.int32),
        c_row_miss=st["c_row_miss"] + (can & ~hit_j).astype(jnp.int32),
    )
    return new


# ---------------------------------------------------------------------------
# Full cycle step + main loop
# ---------------------------------------------------------------------------


def _classify_and_advance(cfg, st, cycle, running, issued_any, n_ready0):
    D, T = st["status"].shape
    runnable = st["status"] == RUN
    ni = jnp.min(jnp.where(runnable, st["next_issue"], INF), -1)
    df = jnp.where(st["eng_active"], st["eng_finish"], INF)
    nxt = jnp.minimum(ni, df)

    port_blocked = st["port_busy"] > 0
    can_skip = (running & ~issued_any & ~port_blocked & cfg.event_skip
                & (nxt < INF))
    new_cycle = jnp.where(
        running, jnp.where(can_skip, jnp.maximum(cycle + 1, nxt), cycle + 1),
        cycle)
    delta = new_cycle - cycle

    idle = running & ~issued_any
    rf = idle & port_blocked & (n_ready0 > 0)
    mem = idle & ~rf & (df <= ni)
    rev = idle & ~rf & ~mem

    c_active = st["c_active"] + issued_any.astype(jnp.int32)
    c_idle_rf = st["c_idle_rf"] + jnp.where(rf, delta, 0)
    c_idle_mem = st["c_idle_mem"] + jnp.where(mem, delta, 0)
    c_idle_rev = st["c_idle_rev"] + jnp.where(rev, delta, 0)

    new = dict(st)
    if cfg.collect_detail:
        hist = lane_add(st["c_hist"], jnp.clip(n_ready0, 0, T),
                        running.astype(jnp.int32))
        hist = lane_add(hist, jnp.zeros_like(n_ready0),
                        jnp.where(running, delta - 1, 0))

        # TLP time series
        win = cfg.timeseries_window
        L = st["ts_buf"].shape[1]
        ts_acc = st["ts_acc"] + n_ready0.astype(jnp.float32)
        w_old = cycle // win
        w_new = new_cycle // win
        crossed = w_new > w_old
        slot = jnp.clip(w_old, 0, L - 1)
        ts_buf = lane_set(st["ts_buf"], slot, ts_acc / win, crossed)
        ts_acc = jnp.where(crossed, 0.0, ts_acc)
        new.update(c_hist=hist, ts_buf=ts_buf, ts_acc=ts_acc)

    new.update(cycle=new_cycle, port_busy=jnp.maximum(st["port_busy"] - 1, 0),
               c_active=c_active, c_idle_mem=c_idle_mem,
               c_idle_rev=c_idle_rev, c_idle_rf=c_idle_rf)
    return new


def make_cond(cfg: DPUConfig):
    """Termination predicate shared by every backend's while-loop driver."""

    def cond(st):
        alive = (st["status"] != DONE).any(-1)
        return (alive & (st["cycle"] < cfg.max_cycles)).any()

    return cond


def make_step_traced(cfg: DPUConfig):
    """One simulated cycle as a pure function ``(ir, state) -> state`` on
    the carry form of the state (:func:`to_carry`).

    ``ir`` is the instruction image (the 6 SoA int32 vectors of
    :class:`isa.Binary`) passed as *traced operands*: the compiled XLA
    executable is binary-agnostic, so every kernel of the same padded
    program shape reuses it (see :mod:`repro.core.compile_cache`)."""

    def step(ir, st):
        cycle = st["cycle"]
        alive = (st["status"] != DONE).any(-1)
        running = alive & (cycle < cfg.max_cycles)

        st = _dram_step(cfg, st, cycle)

        # barrier release
        bar = st["status"] == BLK_BAR
        n_bar = bar.sum(-1)
        n_alive = (st["status"] != DONE).sum(-1)
        rel = (n_bar > 0) & (n_bar == n_alive)
        relm = rel[:, None] & bar
        st = dict(st)
        st["status"] = jnp.where(relm, RUN, st["status"])
        st["next_issue"] = jnp.where(relm, (cycle + 1)[:, None], st["next_issue"])

        ready0 = (st["status"] == RUN) & (st["next_issue"] <= cycle[:, None])
        n_ready0 = ready0.sum(-1)

        issued_any = jnp.zeros_like(running)
        already = None
        slot_block = jnp.zeros_like(running)
        for s in range(cfg.superscalar):
            st, valid, hazard, im = _issue_one(cfg, ir, st, cycle, running,
                                               already, slot_block)
            issued_any = issued_any | valid
            already = im if already is None else (already | im)
            # an RF-hazard instruction consumes the second read slot:
            # block further same-cycle issue too
            slot_block = slot_block | hazard | ~valid

        st = _classify_and_advance(cfg, st, cycle, running, issued_any,
                                   n_ready0)
        return st

    return step


def run(cfg: DPUConfig, binary: isa.Binary, wram_init, mram_init,
        n_threads: int = None, ndpus_reg: int = None):
    """Simulate to completion; returns the final state (host numpy pytree).

    Launches the ``"scalar"`` :class:`repro.core.backend.ExecBackend`
    through :mod:`repro.core.compile_cache`: warm relaunches of any
    kernel with the same padded shape reuse one XLA executable."""
    from repro.core import compile_cache
    return compile_cache.run(cfg, binary, wram_init, mram_init,
                             n_threads=n_threads, backend="scalar",
                             ndpus_reg=ndpus_reg)
