"""Fault injection, retry/backoff, and graceful degradation.

Covers the ``repro.faults`` stack end to end: zero-fault bit-exactness
(pay-for-what-you-use), permanent-death remap oracles on BFS/HST/SSORT,
transient retries priced as goodput loss, MRAM bit flips with and
without ECC, link degradation/timeouts, typed error surfaces, and
same-seed determinism across ``mode="inorder"`` / ``mode="async"``."""
import numpy as np
import pytest

import repro.workloads as wl
from repro.comm import collectives
from repro.core.config import DPUConfig
from repro.core.host import PIMSystem
from repro.faults import (PERFECT_ECC, DpuFaultError, EccModel, FaultEvent,
                          FaultPlan, RetryPolicy, kill_dpu)
from repro.faults.remap import launch_with_remap


def _cfg(**kw):
    base = dict(n_dpus=4, n_tasklets=8, mram_bytes=1 << 21)
    return DPUConfig(**{**base, **kw})


def _hst(cfg, scale=0.02):
    w = wl.get("HST-S")
    hd = w.host_data(cfg, scale=scale, seed=0)
    binary = w.build(8).binary(cfg.iram_instrs)
    return hd, binary


# ---- zero-fault bit-exactness ----------------------------------------------

def test_zero_fault_plan_is_bit_exact():
    """faults=FaultPlan() (all rates zero) must cost nothing and change
    nothing vs faults=None — the fault layer is pay-for-what-you-use."""
    plan = FaultPlan()
    assert plan.is_noop
    results = []
    for faults in (None, plan):
        s = PIMSystem(_cfg(), faults=faults)
        st, _ = wl.get("HST-S").run(s, n_threads=8, scale=0.03)
        results.append((s.timeline.total, s.timeline.breakdown(),
                        np.asarray(st["mram"])))
    (t0, b0, m0), (t1, b1, m1) = results
    assert t0 == t1 and b0 == b1
    assert np.array_equal(m0, m1)
    assert results[1][1]["retry"] == 0.0


def test_timeline_goodput_without_faults_is_one():
    s = PIMSystem(_cfg())
    wl.get("VA").run(s, n_threads=8, scale=0.02)
    assert s.timeline.goodput == 1.0 and s.timeline.retry == 0.0


# ---- permanent faults + remap recovery -------------------------------------

@pytest.mark.parametrize("name,dead,launch,scale", [
    ("BFS", 1, 0, 0.08),
    ("HST-S", 1, 0, 0.03),
    ("SSORT", 2, 1, 0.02),
])
def test_killed_dpu_remap_oracle(name, dead, launch, scale):
    """A DPU dies mid-workload; remap re-executes its shard on survivors
    and the workload's own numpy oracle must still pass."""
    s = PIMSystem(_cfg(), faults=FaultPlan(events=(kill_dpu(dead, launch),)))
    wl.get(name).run(s, n_threads=8, scale=scale)  # oracle inside run()
    assert not s.active_mask[dead]
    assert s.active_dpus == [d for d in range(4) if d != dead]
    assert any(r.kind == "permanent" and dead in r.dpus
               for r in s.fault_log)


def test_killed_root_moves_collective_root():
    """DPU 0 (the default reduce root) dies; HST-S re-roots the merge on
    the first survivor instead of raising dead_root."""
    s = PIMSystem(_cfg(), faults=FaultPlan(events=(kill_dpu(0, 0),)))
    wl.get("HST-S").run(s, n_threads=8, scale=0.03)
    assert s.active_dpus == [1, 2, 3]


def test_undegraded_launch_on_dead_dpu_raises():
    cfg = _cfg()
    hd, binary = _hst(cfg)
    s = PIMSystem(cfg, faults=FaultPlan(events=(kill_dpu(1, 0),)),
                  recovery="raise")
    with pytest.raises(DpuFaultError) as ei:
        s.launch("HST-S", binary, hd.args, hd.mram, n_threads=8)
    assert ei.value.report.kind == "permanent"
    assert 1 in ei.value.report.dpus


def test_remap_with_spares_promotes_lost_shard():
    """4 worker shards on a 6-lane system with 2 spares: the dead lane's
    shard lands on a spare and the merged result passes the oracle."""
    cfg4, cfg6 = _cfg(), _cfg(n_dpus=6)
    hd, binary = _hst(cfg4)
    s = PIMSystem(cfg6, faults=FaultPlan(events=(kill_dpu(1, 0),)))
    args = np.zeros((6, hd.args.shape[1]), np.int32)
    mram = np.zeros((6, hd.mram.shape[1]), np.int32)
    args[:4], mram[:4] = hd.args, hd.mram
    st, _ = launch_with_remap(s, "HST-S", binary, args, mram, n_threads=8,
                              dpus=[0, 1, 2, 3], spares=[4, 5])
    assert hd.check(np.asarray(st["mram"])[:4])
    assert not s.active_mask[1]


def test_remap_checkpoint_roundtrip(tmp_path):
    """ckpt_dir snapshots the launch inputs through repro.ckpt.store and
    re-executes the lost shard from the restored image."""
    cfg = _cfg()
    hd, binary = _hst(cfg)
    s = PIMSystem(cfg, faults=FaultPlan(events=(kill_dpu(2, 0),)))
    st, _ = launch_with_remap(s, "HST-S", binary, hd.args, hd.mram,
                              n_threads=8, ckpt_dir=str(tmp_path))
    assert hd.check(np.asarray(st["mram"]))
    assert any(tmp_path.iterdir()), "checkpoint files were not written"


def test_all_dead_raises_no_active_dpus():
    cfg = _cfg()
    hd, binary = _hst(cfg)
    s = PIMSystem(cfg, faults=FaultPlan())
    s.disable_dpus(range(4))
    with pytest.raises(DpuFaultError) as ei:
        s.launch("HST-S", binary, hd.args, hd.mram, n_threads=8,
                 degraded=True)
    assert ei.value.report.kind == "no_active_dpus"


# ---- transient faults + retry pricing --------------------------------------

def test_transient_fault_retried_and_priced():
    """One transient attempt fault: the retry succeeds, the oracle holds,
    and the wasted attempt lands in the timeline's retry phase (goodput
    strictly between 0 and 1, consistent with the schedule's view)."""
    plan = FaultPlan(events=(FaultEvent("transient", 0, dpu=1),))
    s = PIMSystem(_cfg(), faults=plan)
    wl.get("HST-S").run(s, n_threads=8, scale=0.03)
    assert s.timeline.retry > 0.0
    assert 0.0 < s.timeline.goodput < 1.0
    assert any(r.kind == "transient" for r in s.fault_log)
    sched = s.sync()
    assert np.isclose(sched.wasted(), s.timeline.retry)
    assert sched.goodput() < 1.0


def test_transient_retry_exhausted_raises():
    evs = tuple(FaultEvent("transient", 0, dpu=1, attempt=a)
                for a in range(3))
    cfg = _cfg()
    hd, binary = _hst(cfg)
    s = PIMSystem(cfg, faults=FaultPlan(events=evs))
    with pytest.raises(DpuFaultError) as ei:
        s.launch("HST-S", binary, hd.args, hd.mram, n_threads=8)
    assert ei.value.report.kind == "retry_exhausted"
    assert s.timeline.retry > 0.0  # the dead attempts were still priced


def test_modeled_launch_participates_in_fault_stream():
    plan = FaultPlan(events=(FaultEvent("transient", 0, dpu=0),))
    s = PIMSystem(_cfg(), faults=plan)
    s.modeled_launch("decode", 1e-4)
    assert s.timeline.retry > 0.0 and s.timeline.kernel > 0.0
    s2 = PIMSystem(_cfg(), faults=FaultPlan())
    s2.disable_dpus(range(4))
    with pytest.raises(DpuFaultError):
        s2.modeled_launch("decode", 1e-4)


def test_retry_policy_validation_and_backoff():
    p = RetryPolicy(max_attempts=3, backoff_seconds=1e-6, backoff_factor=2.0)
    assert p.backoff_after(0) == 1e-6 and p.backoff_after(2) == 4e-6
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(backoff_seconds=-1.0)


# ---- MRAM bit flips + ECC --------------------------------------------------

def _flip_event(hd, bit=13):
    # flip a bit inside DPU 0's input array (args row = [n, src, dst])
    word = int(hd.args[0][1]) // 4
    return FaultEvent("bitflip", 0, dpu=0, word=word, bit=bit)


def test_bitflip_without_ecc_corrupts_silently():
    cfg = _cfg()
    hd, binary = _hst(cfg)
    s = PIMSystem(cfg, faults=FaultPlan(events=(_flip_event(hd),)))
    st, _ = s.launch("HST-S", binary, hd.args, hd.mram, n_threads=8)
    assert not hd.check(np.asarray(st["mram"]))  # silent data corruption
    assert any(r.kind == "bitflip" for r in s.fault_log)


def test_bitflip_with_perfect_ecc_corrected_and_priced():
    cfg = _cfg()
    hd, binary = _hst(cfg)
    clean = PIMSystem(cfg)
    st0, _ = clean.launch("HST-S", binary, hd.args, hd.mram, n_threads=8)
    s = PIMSystem(cfg, faults=FaultPlan(ecc=PERFECT_ECC,
                                        events=(_flip_event(hd),)))
    st1, _ = s.launch("HST-S", binary, hd.args, hd.mram, n_threads=8)
    assert hd.check(np.asarray(st1["mram"]))          # corrected in place
    assert np.array_equal(np.asarray(st0["mram"]), np.asarray(st1["mram"]))
    assert s.timeline.kernel > clean.timeline.kernel  # scrub cycles priced


def test_bitflip_detected_scrubs_on_retry():
    """detect-only ECC: the flip raises a transient lane fault; the
    retry re-reads clean data and the oracle passes."""
    cfg = _cfg()
    hd, binary = _hst(cfg)
    ecc = EccModel(p_correct=0.0, p_detect=1.0)
    s = PIMSystem(cfg, faults=FaultPlan(ecc=ecc, events=(_flip_event(hd),)))
    st, _ = s.launch("HST-S", binary, hd.args, hd.mram, n_threads=8)
    assert hd.check(np.asarray(st["mram"]))
    assert s.timeline.retry > 0.0


# ---- link faults -----------------------------------------------------------

def test_link_degradation_scales_transfer_time():
    base = PIMSystem(_cfg())
    base.h2d(4096)
    s = PIMSystem(_cfg(), faults=FaultPlan(p_link_degrade=1.0,
                                           link_degrade_factor=3.0))
    s.h2d(4096)
    assert np.isclose(s.timeline.h2d, 3.0 * base.timeline.h2d)
    assert any(r.kind == "link" and "degraded" in r.detail
               for r in s.fault_log)


def test_link_timeout_retried_then_succeeds():
    plan = FaultPlan(events=(FaultEvent("link", 0, timeout=True),))
    s = PIMSystem(_cfg(), faults=plan)
    s.h2d(4096)
    assert s.timeline.retry > 0.0 and s.timeline.h2d > 0.0
    assert any(r.kind == "link" and r.detail == "timeout"
               for r in s.fault_log)


def test_link_timeout_exhausts_retries():
    evs = tuple(FaultEvent("link", 0, attempt=a, timeout=True)
                for a in range(5))
    s = PIMSystem(_cfg(), faults=FaultPlan(events=evs))
    with pytest.raises(DpuFaultError) as ei:
        s.h2d(4096)
    assert ei.value.report.kind == "retry_exhausted"


# ---- typed error surfaces --------------------------------------------------

def test_launch_empty_and_invalid_dpus_raise_value_error():
    cfg = _cfg()
    hd, binary = _hst(cfg)
    s = PIMSystem(cfg)
    with pytest.raises(ValueError):
        s.launch("HST-S", binary, hd.args, hd.mram, n_threads=8, dpus=[])
    with pytest.raises(ValueError):
        s.launch("HST-S", binary, hd.args, hd.mram, n_threads=8, dpus=[9])
    with pytest.raises(ValueError):
        s.launch("HST-S", binary, hd.args[:3], hd.mram, n_threads=8)


def test_collective_with_dead_root_raises_typed_error():
    s = PIMSystem(_cfg(), faults=FaultPlan())
    s.disable_dpus([0])
    with pytest.raises(DpuFaultError) as ei:
        collectives.reduce(s, np.zeros((4, 8), np.int32), 0, 8,
                           op="sum", root=0)
    assert ei.value.report.kind == "dead_root"


def test_fault_model_validation():
    with pytest.raises(ValueError):
        FaultPlan(p_dpu_permanent=1.5)
    with pytest.raises(ValueError):
        FaultEvent("meteor", 0)
    with pytest.raises(ValueError):
        EccModel(p_correct=0.9, p_detect=0.2)


# ---- determinism across seeds and modes ------------------------------------

def _faulty_run(mode):
    plan = FaultPlan(seed=5, p_dpu_transient=0.2, flips_per_launch=0.5,
                     ecc=PERFECT_ECC, events=(kill_dpu(2, 0),))
    s = PIMSystem(_cfg(), faults=plan, mode=mode)
    st, _ = wl.get("HST-S").run(s, n_threads=8, scale=0.03)
    return ([str(r) for r in s.fault_log], s.timeline.total,
            np.asarray(st["mram"]))


@pytest.mark.parametrize("mode", ["inorder", "async"])
def test_same_seed_same_faults_same_results(mode):
    log0, total0, m0 = _faulty_run(mode)
    log1, total1, m1 = _faulty_run(mode)
    assert log0 == log1 and total0 == total1
    assert np.array_equal(m0, m1)
    assert log0, "plan with nonzero rates should have fired something"


def test_fault_stream_identical_across_modes():
    """inorder and async submit launches/transfers in the same eager
    program order, so the same plan fires bit-identical fault streams."""
    log_in, total_in, m_in = _faulty_run("inorder")
    log_as, total_as, m_as = _faulty_run("async")
    assert log_in == log_as
    assert total_in == total_as  # serialized sum; overlap only moves elapsed
    assert np.array_equal(m_in, m_as)


# ---- serving: a degraded PIM pool refuses to tick ---------------------------

def test_serve_engine_survives_midstream_pool_fault():
    """A decode pool's healthy tick charges the timeline's kernel phase,
    a degraded one stretches by ``D / healthy``, and once ``disable_dpus``
    drops the pool below ``min_fraction`` mid-stream, ``tick`` raises
    :class:`DpuFaultError` and charges nothing, so its holder can
    reschedule the stream instead of losing it."""
    from repro.serve.pim_pool import PimDecodePool

    pim = PIMSystem(_cfg(), faults=FaultPlan())
    pool = PimDecodePool(pim, tick_seconds=1e-4, min_fraction=0.5)
    assert pool.tick() == 1e-4                  # healthy tick
    assert pim.timeline.kernel == pytest.approx(1e-4)
    pim.disable_dpus([0])                       # 3 of 4 lanes survive
    assert pool.tick() == pytest.approx(1e-4 / 0.75)
    charged = pim.timeline.kernel
    assert charged == pytest.approx(1e-4 + 1e-4 / 0.75)
    pim.disable_dpus([1, 2])                    # 1 of 4: under the floor
    with pytest.raises(DpuFaultError) as ei:
        pool.tick()
    assert ei.value.report.kind == "pool_degraded"
    assert pim.timeline.kernel == charged and pool.ticks == 2
