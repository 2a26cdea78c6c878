"""SIMT vector DPU (case study #1, Fig. 11).

The same uPIM binary executes on an N-way SIMT pipeline: N consecutive
tasklets form a warp; each cycle one ready warp issues, lanes whose PC
equals the warp's minimum PC execute in lockstep (post-Volta style
independent-thread reconvergence), others are masked.  Lane DMA requests
are merged by the optional memory address coalescer (AC): with AC the
per-warp DRAM occupancy pays one activate per *unique row* touched; without
AC lanes are serviced back-to-back, paying an activate whenever consecutive
lanes touch different rows.  MRAM streaming bandwidth is shared either way
(``mram_bw_scale`` scales it for the SIMT+AC+4x/16x design points).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, isa
from repro.core.config import DPUConfig
from repro.core.engine import BLK_BAR, BLK_DMA, DONE, INF, RUN, alu_exec
from repro.core.isa import Op


def make_state_np(cfg: DPUConfig, binary, wram_init, mram_init,
                  n_threads=None):
    st = engine.make_state_np(cfg, binary, wram_init, mram_init, n_threads)
    D = cfg.n_dpus
    T = st["status"].shape[1]
    n_warps = T // cfg.simt_width
    st["warp_next"] = np.zeros((D, n_warps), np.int32)
    st["req_service"] = np.zeros((D, T), np.int32)
    return st


def make_state(cfg: DPUConfig, binary, wram_init, mram_init, n_threads=None):
    return jax.tree_util.tree_map(
        jnp.asarray, make_state_np(cfg, binary, wram_init, mram_init,
                                   n_threads))


def _dram_step(cfg: DPUConfig, st, cycle):
    """FR-FCFS with precomputed per-request service; wakes the whole warp."""
    D, T = st["status"].shape
    W = cfg.simt_width
    dd = jnp.arange(D)

    comp = st["eng_active"] & (st["eng_finish"] <= cycle)
    leader = st["eng_thread"]
    warp = leader // W
    lane_warp = jnp.arange(T)[None, :] // W
    wake = comp[:, None] & (lane_warp == warp[:, None]) & (st["status"] == BLK_DMA)
    status = jnp.where(wake, RUN, st["status"])
    next_issue = jnp.where(wake, (cycle + 1)[:, None], st["next_issue"])
    req_valid = st["req_valid"].at[dd, leader].set(
        jnp.where(comp, False, st["req_valid"][dd, leader]))
    eng_active = st["eng_active"] & ~comp

    can = ~eng_active & req_valid.any(-1)
    row = st["req_mram"] // cfg.row_bytes
    hit = row == st["open_row"][:, None]
    score = jnp.where(req_valid, hit.astype(jnp.int32) * INF - st["req_enq"], -INF)
    j = jnp.argmax(score, -1)
    service = st["req_service"][dd, j]
    end_row = (st["req_mram"][dd, j] + jnp.maximum(st["req_bytes"][dd, j], 1) - 1
               ) // cfg.row_bytes

    new = dict(st)
    new.update(
        status=status, next_issue=next_issue, req_valid=req_valid,
        eng_active=eng_active | can,
        eng_thread=jnp.where(can, j, st["eng_thread"]),
        eng_finish=jnp.where(can, cycle + service, st["eng_finish"]),
        open_row=jnp.where(can, end_row, st["open_row"]),
        c_row_hit=st["c_row_hit"] + (can & hit[dd, j]).astype(jnp.int32),
        c_row_miss=st["c_row_miss"] + (can & ~hit[dd, j]).astype(jnp.int32),
    )
    return new


def make_step_traced(cfg: DPUConfig):
    """One SIMT cycle as a pure function ``(ir, state) -> state`` with the
    instruction image as traced operands (see ``engine.make_step_traced``)."""
    W = cfg.simt_width

    def step(ir, st):
        iop, ird, ira, irb, iimm, iui = ir
        cycle = st["cycle"]
        D, T = st["status"].shape
        nW = T // W
        dd = jnp.arange(D)
        alive = (st["status"] != DONE).any(-1)
        running = alive & (cycle < cfg.max_cycles)

        st = _dram_step(cfg, st, cycle)

        # barrier release (all live lanes arrived)
        bar = st["status"] == BLK_BAR
        rel = (bar.sum(-1) > 0) & (bar.sum(-1) == (st["status"] != DONE).sum(-1))
        relm = rel[:, None] & bar
        st = dict(st)
        st["status"] = jnp.where(relm, RUN, st["status"])

        status_w = st["status"].reshape(D, nW, W)
        pc_w = st["pc"].reshape(D, nW, W)
        blocked = ((status_w == BLK_DMA) | (status_w == BLK_BAR)).any(-1)
        has_run = (status_w == RUN).any(-1)
        warp_ready = has_run & ~blocked & (st["warp_next"] <= cycle[:, None]) \
            & running[:, None]
        n_ready0 = jnp.where(warp_ready, (status_w == RUN).sum(-1), 0).sum(-1)

        prio = (jnp.arange(nW)[None, :] - st["rr"][:, None]) % nW
        wsel = jnp.argmin(jnp.where(warp_ready, prio, INF), -1)
        valid = warp_ready.any(-1)

        lanes = wsel[:, None] * W + jnp.arange(W)[None, :]      # (D, W)
        lane_stat = st["status"][dd[:, None], lanes]
        lane_pc = st["pc"][dd[:, None], lanes]
        warp_pc = jnp.min(jnp.where(lane_stat == RUN, lane_pc, INF), -1)
        warp_pc_c = jnp.clip(warp_pc, 0, iop.shape[0] - 1)
        active = (lane_stat == RUN) & (lane_pc == warp_pc[:, None]) \
            & valid[:, None]

        op = iop[warp_pc_c]          # (D,)
        rdv, rav, rbv = ird[warp_pc_c], ira[warp_pc_c], irb[warp_pc_c]
        immv, uiv = iimm[warp_pc_c], iui[warp_pc_c] != 0

        regs = st["regs"]
        a = regs[dd[:, None], lanes, rav[:, None]]               # (D, W)
        breg = regs[dd[:, None], lanes, rbv[:, None]]
        b = jnp.where(uiv[:, None], immv[:, None], breg)

        opw = op[:, None]
        alu = alu_exec(jnp.broadcast_to(opw, a.shape), a, b)
        addr = a + immv[:, None]
        widx = jnp.clip(addr >> 2, 0, st["wram"].shape[1] - 1)
        ldval = st["wram"][dd[:, None], widx]
        res = jnp.where(opw <= Op.SLTU, alu,
              jnp.where(opw == Op.LW, ldval, warp_pc[:, None] + 1))

        writes = jnp.asarray(isa.WRITES_RD)[op][:, None] & active
        dst = jnp.where(writes, rdv[:, None], 0)
        cur = regs[dd[:, None], lanes, dst]
        regs = regs.at[dd[:, None], lanes, dst].set(jnp.where(writes, res, cur))

        do_sw = active & (opw == Op.SW)
        wram = st["wram"].at[dd[:, None], jnp.where(do_sw, widx, 1 << 30)].set(
            breg, mode="drop")

        # ---- atomics: lane-serialized (lowest active lane wins per cycle) ----
        mid = jnp.clip(immv, 0, st["atomic"].shape[1] - 1)
        is_acq = opw == Op.ACQUIRE
        first_active = jnp.argmax(active, -1)
        is_first = jnp.arange(W)[None, :] == first_active[:, None]
        held = st["atomic"][dd, mid] != 0
        acq_ok = active & is_acq & is_first & ~held[:, None]
        rel_op = active & (opw == Op.RELEASE)
        aval = jnp.where(acq_ok.any(-1), 1,
                         jnp.where(rel_op.any(-1), 0, st["atomic"][dd, mid]))
        atomic = st["atomic"].at[dd, mid].set(aval)
        acq_stall = active & is_acq & ~acq_ok

        # ---- DMA: merge lane requests (coalescer) ----
        do_dma = active & ((opw == Op.LDMA) | (opw == Op.SDMA))
        any_dma = do_dma.any(-1)
        size = jnp.where(uiv[:, None], immv[:, None],
                         regs[dd[:, None], lanes, rdv[:, None]])
        size = jnp.clip(jnp.where(do_dma, size, 0), 0, engine.MAX_DMA_BYTES)
        rows = jnp.where(do_dma, breg // cfg.row_bytes, -1)
        total_bytes = size.sum(-1)
        if cfg.coalescing:
            # one activate per unique row among lanes
            uniq = jnp.zeros(D, jnp.int32)
            for l in range(W):
                seen = jnp.zeros(D, bool)
                for m in range(l):
                    seen = seen | (do_dma[:, m] & (rows[:, m] == rows[:, l]))
                uniq = uniq + (do_dma[:, l] & ~seen).astype(jnp.int32)
            overhead = uniq * cfg.row_miss_overhead
            # merged row-bursts stream at bank burst bandwidth
            bw = cfg.effective_mram_bw * cfg.coalesced_bw_mult
        else:
            # naive SIMT: every lane's request is an independent transaction
            overhead = do_dma.sum(-1) * cfg.row_miss_overhead
            bw = cfg.effective_mram_bw
        transfer = jnp.ceil(total_bytes / bw).astype(jnp.int32)
        service = overhead + transfer

        leader = wsel * W + first_active
        req_valid = st["req_valid"].at[dd, leader].set(
            st["req_valid"][dd, leader] | any_dma)
        req_mram = st["req_mram"].at[dd, leader].set(
            jnp.where(any_dma, breg[dd, first_active], st["req_mram"][dd, leader]))
        req_bytes = st["req_bytes"].at[dd, leader].set(
            jnp.where(any_dma, total_bytes, st["req_bytes"][dd, leader]))
        req_enq = st["req_enq"].at[dd, leader].set(
            jnp.where(any_dma, cycle, st["req_enq"][dd, leader]))
        req_service = st["req_service"].at[dd, leader].set(
            jnp.where(any_dma, service, st["req_service"][dd, leader]))
        is_w = opw == Op.SDMA

        # functional lane copies.  Masked slots are scattered with
        # out-of-bounds indices + mode="drop": lanes write concurrently, so
        # a masked write-back of a stale value could otherwise race with
        # another lane's real write to the same address.
        def do_copy(wm):
            wram_, mram_ = wm
            nw = engine.MAX_DMA_BYTES // 4
            k = jnp.arange(nw)
            wb = (jnp.where(do_dma, a, 0) >> 2)[..., None] + k
            mb = (jnp.where(do_dma, breg, 0) >> 2)[..., None] + k
            nwords = (size + 3) >> 2
            mask = k[None, None, :] < nwords[..., None]
            wb = jnp.clip(wb, 0, wram_.shape[1] - 1)
            mb = jnp.clip(mb, 0, mram_.shape[1] - 1)
            ddk = dd[:, None, None]
            rd_m = mram_[ddk, mb]
            rd_w = wram_[ddk, wb]
            ldm = mask & (do_dma & ~is_w)[..., None]
            stm = mask & (do_dma & is_w)[..., None]
            OOB = 1 << 30
            wram_ = wram_.at[ddk, jnp.where(ldm, wb, OOB)].set(
                rd_m, mode="drop")
            mram_ = mram_.at[ddk, jnp.where(stm, mb, OOB)].set(
                rd_w, mode="drop")
            return wram_, mram_

        wram, mram = jax.lax.cond(any_dma.any(), do_copy, lambda wm: wm,
                                  (wram, st["mram"]))

        # ---- control flow / status ----
        eq, lt = a == b, a < b
        ltu = a.astype(jnp.uint32) < b.astype(jnp.uint32)
        taken = jnp.select(
            [opw == o for o in (Op.BEQ, Op.BNE, Op.BLT, Op.BGE, Op.BLTU,
                                Op.BGEU)],
            [eq, ~eq, lt, ~lt, ltu, ~ltu], False)
        pc1 = warp_pc[:, None] + 1
        new_pc = jnp.where((opw >= Op.BEQ) & (opw <= Op.BGEU),
                           jnp.where(taken, immv[:, None], pc1),
                  jnp.where((opw == Op.JUMP) | (opw == Op.JAL), immv[:, None],
                  jnp.where(opw == Op.JR, a,
                  jnp.where(acq_stall | (opw == Op.STOP), warp_pc[:, None],
                            pc1))))
        pc = st["pc"].at[dd[:, None], lanes].set(
            jnp.where(active, new_pc, lane_pc))

        new_stat = jnp.where(active & (opw == Op.STOP), DONE,
                   jnp.where(do_dma, BLK_DMA,
                   jnp.where(active & (opw == Op.BARRIER), BLK_BAR, lane_stat)))
        status = st["status"].at[dd[:, None], lanes].set(new_stat)

        gap = 1 + jnp.where(op == Op.MUL, cfg.mul_extra,
                  jnp.where(op == Op.DIV, cfg.div_extra, 0))
        warp_next = st["warp_next"].at[dd, wsel].set(
            jnp.where(valid, cycle + gap, st["warp_next"][dd, wsel]))
        rr = jnp.where(valid, (wsel + 1) % nW, st["rr"])

        n_active = active.sum(-1)
        cls = jnp.asarray(isa.OP_CLASS_TABLE)[op]
        c_cls = st["c_cls"].at[dd, jnp.where(valid, cls, 0)].add(
            jnp.where(valid, n_active, 0))

        st.update(
            regs=regs, wram=wram, mram=mram, atomic=atomic, pc=pc,
            status=status, warp_next=warp_next, rr=rr,
            req_valid=req_valid, req_mram=req_mram, req_bytes=req_bytes,
            req_enq=req_enq, req_service=req_service,
            c_issued=st["c_issued"] + jnp.where(valid, n_active, 0),
            c_cls=c_cls,
            c_acq_retry=st["c_acq_retry"] + acq_stall.sum(-1),
            c_dma_rd=st["c_dma_rd"] + (do_dma & ~is_w).sum(-1),
            c_dma_wr=st["c_dma_wr"] + (do_dma & is_w).sum(-1),
            c_dma_rd_bytes=st["c_dma_rd_bytes"]
            + jnp.where(do_dma & ~is_w, size, 0).sum(-1).astype(jnp.float32),
            c_dma_wr_bytes=st["c_dma_wr_bytes"]
            + jnp.where(do_dma & is_w, size, 0).sum(-1).astype(jnp.float32),
        )

        # ---- classify + advance (warp-level events) ----
        runnable_w = has_run & ~blocked
        ni = jnp.min(jnp.where(runnable_w, st["warp_next"], INF), -1)
        df = jnp.where(st["eng_active"], st["eng_finish"], INF)
        nxt = jnp.minimum(ni, df)
        issued_any = valid
        can_skip = running & ~issued_any & cfg.event_skip & (nxt < INF)
        new_cycle = jnp.where(
            running, jnp.where(can_skip, jnp.maximum(cycle + 1, nxt), cycle + 1),
            cycle)
        delta = new_cycle - cycle
        idle = running & ~issued_any
        mem = idle & (df <= ni)
        st.update(
            cycle=new_cycle,
            c_active=st["c_active"] + issued_any.astype(jnp.int32),
            c_idle_mem=st["c_idle_mem"] + jnp.where(mem, delta, 0),
            c_idle_rev=st["c_idle_rev"] + jnp.where(idle & ~mem, delta, 0),
            c_hist=st["c_hist"].at[dd, jnp.clip(n_ready0, 0, T)].add(
                running.astype(jnp.int32)),
        )
        return st

    return step


def run(cfg: DPUConfig, binary, wram_init, mram_init, n_threads=None,
        ndpus_reg=None):
    """Simulate on the ``"simt"`` :class:`repro.core.backend.ExecBackend`
    (its ``validate`` enforces ``simt_width > 0`` and warp-divisible
    tasklet counts) through the compiled-engine cache."""
    from repro.core import compile_cache
    return compile_cache.run(cfg, binary, wram_init, mram_init,
                             n_threads=n_threads, backend="simt",
                             ndpus_reg=ndpus_reg)
