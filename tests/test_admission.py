"""repro.admission: typed backpressure, deadline shedding, hedged
launches, circuit breakers, and crash-consistent journal resume."""
import numpy as np
import pytest

from repro.admission import (AdmissionPolicy, CircuitBreaker,
                             ClusterJournal, HedgePolicy, RankBreakers,
                             SimulatedCrash, TokenBucket)
from repro.cluster import (COMPLETED, REJECTED, SHED, JobSpec, PimCluster,
                           TenantSpec, poisson_stream, scale_rates,
                           trace_profile)
from repro.core.config import DPUConfig
from repro.core.host import PIMSystem
from repro.faults.model import FaultPlan
from repro.faults.retry import RetryPolicy


def _sys(D=32, ranks=8, chans=4, mode="async", faults=None):
    return PIMSystem(DPUConfig(n_dpus=D, n_ranks=ranks, n_channels=chans,
                               mram_bytes=1 << 20),
                     mode=mode, faults=faults)


def _burst(n, tenant="t", kind="HST-S", n_ranks=1, slo=np.inf, spacing=0.0):
    return [JobSpec(jid=j, tenant=tenant, kind=kind,
                    arrival=j * spacing, n_ranks=n_ranks,
                    slo_seconds=slo)
            for j in range(n)]


# ---------------------------------------------------------------------------
# policy objects: validation + token-bucket math
# ---------------------------------------------------------------------------

def test_policy_validation():
    with pytest.raises(ValueError):
        AdmissionPolicy(max_queue=0)
    with pytest.raises(ValueError):
        AdmissionPolicy(rate_limits={"t": (0.0, 4.0)})
    with pytest.raises(ValueError):
        AdmissionPolicy(rate_limits={"t": (10.0, 0.5)})
    with pytest.raises(ValueError):
        HedgePolicy(factor=1.0)                # would hedge every step
    with pytest.raises(ValueError):
        CircuitBreaker(min_samples=8, window=4)
    with pytest.raises(ValueError):
        CircuitBreaker(trip_rate=0.0)
    with pytest.raises(ValueError):
        TokenBucket(rate_hz=5.0, burst=0.5)


def test_token_bucket_is_pure_function_of_query_times():
    b = TokenBucket(rate_hz=10.0, burst=2.0)
    assert b.try_take(0.0) and b.try_take(0.0)     # burst drained
    assert not b.try_take(0.0)
    assert b.retry_after() == pytest.approx(0.1)   # 1 token at 10 Hz
    assert not b.try_take(0.05)                    # half a token: still dry
    assert b.try_take(0.1)                         # refilled exactly one
    # time never goes backwards inside the bucket
    assert not b.try_take(0.05)
    b2 = TokenBucket(rate_hz=10.0, burst=2.0)
    seq = [b2.try_take(t) for t in (0.0, 0.0, 0.0, 0.1)]
    b3 = TokenBucket(rate_hz=10.0, burst=2.0)
    assert seq == [b3.try_take(t) for t in (0.0, 0.0, 0.0, 0.1)]


def test_empty_policy_admits_everything():
    rep = PimCluster(_sys(), policy="first_fit",
                     admission=AdmissionPolicy()).run(_burst(6))
    assert all(o.status == COMPLETED for o in rep.outcomes)


# ---------------------------------------------------------------------------
# cluster admission: bounded queue + per-tenant rate limits
# ---------------------------------------------------------------------------

def test_queue_bound_rejects_typed_and_free():
    # 12 simultaneous fleet-wide jobs, queue bounded at 2: the first
    # runs, two wait, the rest bounce without consuming any capacity
    jobs = _burst(12, n_ranks=8)
    rep = PimCluster(_sys(), policy="first_fit",
                     admission=AdmissionPolicy(max_queue=2)).run(jobs)
    by_status = {}
    for o in rep.outcomes:
        by_status.setdefault(o.status, []).append(o)
    assert len(by_status[COMPLETED]) == 3
    assert len(by_status[REJECTED]) == 9
    for o in by_status[REJECTED]:
        assert o.reason == "queue_full"
        assert o.t_start is None and o.spent == 0.0 and o.useful == 0.0
    m = rep.metrics()
    assert m["rejected"] == 9 and m["completed"] == 3
    # rejected work never dilutes goodput: everything spent was useful
    assert rep.goodput() == 1.0


def test_rate_limit_rejects_only_the_offending_tenant():
    jobs = sorted(_burst(6, tenant="greedy")
                  + [JobSpec(jid=10 + j, tenant="calm", kind="BFS",
                             arrival=j * 1e-5) for j in range(3)],
                  key=lambda s: (s.arrival, s.jid))
    pol = AdmissionPolicy(rate_limits={"greedy": (100.0, 2.0)})
    rep = PimCluster(_sys(), policy="first_fit", admission=pol).run(jobs)
    by = {o.jid: o for o in rep.outcomes}
    greedy = [by[j.jid] for j in jobs if j.tenant == "greedy"]
    assert sum(o.status == REJECTED for o in greedy) == 4  # burst of 2
    assert all(o.reason == "rate_limited"
               for o in greedy if o.status == REJECTED)
    assert all(by[10 + j].status == COMPLETED for j in range(3))


def test_backpressure_snapshot():
    pol = AdmissionPolicy(max_queue=4, rate_limits={"t": (50.0, 3.0)})
    cluster = PimCluster(_sys(), policy="first_fit", admission=pol)
    bp = cluster.backpressure()
    assert bp["queue_depth"] == 0 and bp["max_queue"] == 4
    assert bp["quarantined"] == []
    assert bp["tokens"]["t"] == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# deadline shedding (cluster) + serve-engine backpressure
# ---------------------------------------------------------------------------

def test_shedding_drops_doomed_jobs_early():
    # fleet-wide jobs with an SLO only the first can meet: FIFO runs
    # them all hopelessly late, shedding refuses to burn the capacity
    jobs = _burst(8, n_ranks=8, slo=2e-3)
    fifo = PimCluster(_sys(), policy="first_fit").run(jobs)
    shed = PimCluster(_sys(), policy="first_fit", shedding=True).run(jobs)
    assert fifo.metrics()["shed"] == 0
    m = shed.metrics()
    assert m["shed"] > 0
    for o in shed.outcomes:
        if o.status == SHED:
            assert o.reason == "deadline"
    # every completion the shedding cluster kept met its SLO
    done = [o for o in shed.outcomes if o.status == COMPLETED]
    assert done and all(o.slo_met for o in done)
    assert m["slo_goodput"] >= fifo.metrics()["slo_goodput"]


def test_slo_goodput_bounded_by_goodput():
    jobs = _burst(8, n_ranks=8, slo=2e-3)
    rep = PimCluster(_sys(), policy="first_fit").run(jobs)
    m = rep.metrics()
    assert m["slo_goodput"] <= m["goodput"] + 1e-12
    # fault-free underloaded run: both are exactly 1
    easy = PimCluster(_sys(), policy="first_fit").run(_burst(2))
    assert easy.metrics()["slo_goodput"] == 1.0


def test_scale_rates():
    tenants = [TenantSpec("a", rate_hz=100.0, kinds=("BFS",)),
               TenantSpec("b", rate_hz=40.0, kinds=("HST-S",))]
    up = scale_rates(tenants, 1.5)
    assert [t.rate_hz for t in up] == [150.0, 60.0]
    assert [t.name for t in up] == ["a", "b"]
    assert tenants[0].rate_hz == 100.0         # originals untouched
    with pytest.raises(ValueError):
        scale_rates(tenants, 0.0)


# ---------------------------------------------------------------------------
# hedged launches
# ---------------------------------------------------------------------------

def _hedge_jobs():
    tenants = [TenantSpec("a", rate_hz=150.0, kinds=("BFS",),
                          slo_seconds=0.05),
               TenantSpec("b", rate_hz=120.0, kinds=("HST-S",),
                          slo_seconds=0.05)]
    return poisson_stream(tenants, horizon=0.05, seed=11)


def _hedge_run(mode="async", hedge=HedgePolicy(factor=2.5)):
    faults = FaultPlan(seed=3, p_link_degrade=0.25,
                       link_degrade_factor=8.0)
    return PimCluster(_sys(mode=mode, faults=faults),
                      policy="fault_aware", hedge=hedge).run(_hedge_jobs())


def test_hedge_fires_and_cuts_tail_latency():
    hedged, plain = _hedge_run(), _hedge_run(hedge=None)
    mh, mp = hedged.metrics(), plain.metrics()
    assert mh["hedges"] > 0
    assert mp["hedges"] == 0
    assert mh["p99_latency"] < mp["p99_latency"]


def test_hedge_is_cancel_priced():
    faults = FaultPlan(seed=3, p_link_degrade=0.25,
                       link_degrade_factor=8.0)
    cluster = PimCluster(_sys(faults=faults), policy="fault_aware",
                         hedge=HedgePolicy(factor=2.5))
    rep = cluster.run(_hedge_jobs())
    # the duplicate's seconds are charged to the shed phase, and every
    # hedged job paid for both sides: spent strictly exceeds useful
    assert cluster.system.timeline.shed > 0.0
    hedged = [o for o in rep.outcomes if o.hedges > 0]
    assert hedged
    for o in hedged:
        assert o.spent > o.useful
        assert o.hedge_wins <= o.hedges
    assert rep.goodput() < 1.0


def test_hedge_bit_deterministic_across_modes():
    a, b = _hedge_run("inorder"), _hedge_run("async")
    assert a.admissions == b.admissions
    assert a.outcomes == b.outcomes
    assert a.rank_busy == b.rank_busy
    assert a.metrics() == b.metrics()


def test_hedge_policy_trigger_and_profile_floor():
    pol = HedgePolicy(factor=2.0, min_seconds=1e-3)
    assert pol.trigger(1e-4) == 1e-3           # floor dominates
    assert pol.trigger(1.0) == 2.0
    from repro.cluster import synthetic_profiles
    prof = synthetic_profiles()["BFS"]
    derived = HedgePolicy.from_profile(prof, quantile=95.0)
    assert derived.min_seconds > 0.0


def test_retry_worst_case_is_the_hedge_envelope():
    pol = RetryPolicy(max_attempts=3, backoff_seconds=1e-6,
                      backoff_factor=2.0)
    # ideal + 2 failed tries + backoffs 1us + 2us
    assert pol.worst_case_seconds(1e-3) == pytest.approx(3e-3 + 3e-6)
    clipped = RetryPolicy(max_attempts=3, backoff_seconds=1e-6,
                          timeout_seconds=1e-4)
    assert clipped.worst_case_seconds(1e-3) == pytest.approx(
        1e-3 + 2e-4 + 3e-6)
    with pytest.raises(ValueError):
        pol.worst_case_seconds(-1.0)


# ---------------------------------------------------------------------------
# circuit breakers
# ---------------------------------------------------------------------------

def test_breaker_trip_probe_restore_reopen():
    br = RankBreakers(CircuitBreaker(window=4, trip_rate=0.5,
                                     min_samples=2, cooldown_seconds=1.0),
                      n_ranks=2)
    assert br.record(0, False, 0.0) is None    # below min_samples
    assert br.record(0, False, 0.1) == "tripped"
    assert br.state(0, 0.5) == "open" and br.quarantined(0, 0.5)
    assert br.cooldown_until(0) == pytest.approx(1.1)
    assert br.quarantined_ranks(0.5) == [0]
    # outcomes while open neither close nor extend the quarantine
    assert br.record(0, True, 0.5) is None
    assert br.cooldown_until(0) == pytest.approx(1.1)
    # cooldown elapsed: half-open; a failed probe reopens with a
    # doubled cooldown, a clean one restores
    assert not br.quarantined(0, 1.2)
    assert br.state(0, 1.2) == "half_open"
    assert br.record(0, False, 1.2) == "reopened"
    assert br.cooldown_until(0) == pytest.approx(3.2)   # 2x cooldown
    assert br.record(0, True, 3.3) == "restored"
    assert br.state(0, 3.3) == "closed"
    # rank 1 never tripped
    assert br.state(1, 99.0) == "closed" and not br.quarantined(1, 99.0)


def test_breaker_excludes_rank_from_placement():
    cluster = PimCluster(_sys(D=16, ranks=4, chans=2),
                         policy="fault_aware",
                         breaker=CircuitBreaker(min_samples=2,
                                                trip_rate=0.5,
                                                cooldown_seconds=10.0))
    for _ in range(3):
        cluster.breakers.record(0, False, 0.0)
    lease = cluster.lease("svc", n_ranks=2)
    assert 0 not in lease.ranks
    moved = cluster.relocate(lease)
    assert 0 not in moved.ranks
    cluster.release(moved)
    bp = cluster.backpressure()
    assert bp["quarantined"] == [0]


def test_breaker_cluster_run_deterministic_across_modes():
    def run(mode):
        faults = FaultPlan(seed=3, p_dpu_permanent=0.01,
                           p_link_degrade=0.1, link_degrade_factor=6.0)
        return PimCluster(
            _sys(mode=mode, faults=faults), policy="fault_aware",
            breaker=CircuitBreaker(window=8, trip_rate=0.6,
                                   min_samples=4)).run(_hedge_jobs())
    a, b = run("inorder"), run("async")
    assert a.outcomes == b.outcomes and a.metrics() == b.metrics()


# ---------------------------------------------------------------------------
# crash-consistent journal resume
# ---------------------------------------------------------------------------

def _journal_cluster(mode, journal=None, crash_after=None):
    faults = FaultPlan(seed=3, p_dpu_permanent=0.01,
                       p_link_degrade=0.1, link_degrade_factor=6.0)
    return PimCluster(
        _sys(mode=mode, faults=faults), policy="fault_aware",
        admission=AdmissionPolicy(max_queue=6), shedding=True,
        hedge=HedgePolicy(factor=2.5),
        breaker=CircuitBreaker(window=8, trip_rate=0.6, min_samples=4),
        journal=journal, crash_after=crash_after)


def _journal_jobs():
    tenants = [TenantSpec("a", rate_hz=500.0, kinds=("BFS", "HST-S"),
                          priority=1, slo_seconds=0.05),
               TenantSpec("b", rate_hz=300.0, kinds=("lm_decode",),
                          size=4, slo_seconds=0.04)]
    return poisson_stream(tenants, horizon=0.03, seed=11)


def _state(rep):
    return (rep.admissions, rep.outcomes,
            tuple(sorted(rep.rank_busy.items())), rep.makespan,
            tuple(sorted(rep.metrics().items())))


@pytest.mark.parametrize("mode", ["inorder", "async"])
@pytest.mark.parametrize("crash_after", [3, 11])
def test_kill_and_resume_bit_identical(tmp_path, mode, crash_after):
    jobs = _journal_jobs()
    ref = _journal_cluster(mode).run(jobs)
    path = str(tmp_path / "cluster.journal")
    with pytest.raises(SimulatedCrash):
        _journal_cluster(mode, journal=path, crash_after=crash_after) \
            .run(jobs)
    resumed = _journal_cluster(mode, journal=path).run(jobs)
    assert _state(resumed) == _state(ref)


def test_resume_with_lease_replays_placement(tmp_path):
    jobs = _journal_jobs()
    ref_cluster = _journal_cluster("async")
    ref_lease = ref_cluster.lease("svc", n_ranks=2)
    ref = ref_cluster.run(jobs)
    path = str(tmp_path / "cluster.journal")
    crashed = _journal_cluster("async", journal=path, crash_after=8)
    crashed.lease("svc", n_ranks=2)
    with pytest.raises(SimulatedCrash):
        crashed.run(jobs)
    resumed_cluster = _journal_cluster("async", journal=path)
    lease = resumed_cluster.lease("svc", n_ranks=2)
    assert lease.ranks == ref_lease.ranks      # replayed, not re-placed
    resumed = resumed_cluster.run(jobs)
    assert _state(resumed) == _state(ref)
    # a lease outliving the crashed run releases cleanly on the resume
    resumed_cluster.release(lease)
    resumed_cluster.release(lease)             # double release: no-op


def test_journal_torn_tail_dropped_mid_file_corruption_raises(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = ClusterJournal(path)
    j.write({"type": "header", "v": 1})
    j.write({"type": "step", "jid": 0})
    j.close()
    with open(path, "a") as f:
        f.write('{"type": "step", "jid": 1, "del')   # torn final line
    recs = ClusterJournal.load(path)
    assert [r["type"] for r in recs] == ["header", "step"]
    with open(path, "w") as f:
        f.write('{"type": "header"}\nGARBAGE\n{"type": "step"}\n')
    with pytest.raises(ValueError):
        ClusterJournal.load(path)
    assert ClusterJournal.load(str(tmp_path / "missing.jsonl")) == []


def test_resume_detects_divergent_stream(tmp_path):
    jobs = _journal_jobs()
    path = str(tmp_path / "cluster.journal")
    with pytest.raises(SimulatedCrash):
        _journal_cluster("async", journal=path, crash_after=8).run(jobs)
    other = [JobSpec(jid=j.jid, tenant=j.tenant, kind="HST-S",
                     arrival=j.arrival, size=j.size, n_ranks=j.n_ranks,
                     priority=j.priority, slo_seconds=j.slo_seconds)
             for j in jobs]
    with pytest.raises(RuntimeError):
        _journal_cluster("async", journal=path).run(other)


def test_crash_after_requires_journal():
    with pytest.raises(ValueError):
        PimCluster(_sys(), policy="first_fit", crash_after=5)


# ---------------------------------------------------------------------------
# zero-overhead defaults + replay-driven profiles
# ---------------------------------------------------------------------------

def test_all_default_knobs_bit_exact_vs_plain_cluster():
    jobs = _journal_jobs()
    plain = PimCluster(_sys(), policy="fault_aware",
                       spare_ranks=2).run(jobs)
    cluster = PimCluster(_sys(), policy="fault_aware", spare_ranks=2,
                         admission=None, shedding=False, hedge=None,
                         breaker=None, journal=None)
    knobbed = cluster.run(jobs)
    assert _state(plain) == _state(knobbed)
    assert cluster.system.timeline.shed == 0.0


def test_trace_profile_from_recording(tmp_path):
    from repro import trace
    from repro.workloads import get
    system = _sys(D=8, ranks=2, chans=2, mode="inorder")
    rec = trace.record(system)
    get("BFS").run(system, 8, scale=0.02, seed=0)
    system.sync()
    path = str(tmp_path / "bfs.trace.jsonl")
    rec.save(path)
    prof = trace_profile(path, kind="BFS")
    assert prof.steps
    assert any(s.phase == "kernel" for s in prof.steps)
    assert all(s.seconds >= 0.0 for s in prof.steps)
    # the distilled profile drives a cluster run end to end
    rep = PimCluster(_sys(), policy="first_fit",
                     profiles={"BFS": prof}).run(
        [JobSpec(jid=0, tenant="t", kind="BFS", arrival=0.0)])
    assert rep.outcomes[0].status == COMPLETED
    assert rep.outcomes[0].spent > 0.0
    with pytest.raises(ValueError):
        trace_profile([], kind="empty")
