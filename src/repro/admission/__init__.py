"""repro.admission — overload robustness for the cluster runtime.

Four composable mechanisms, all default-off and zero-cost when unused:

* :class:`AdmissionPolicy` / :class:`TokenBucket` — bounded queues and
  per-tenant rate limits turning overload into ``rejected`` outcomes
  instead of unbounded queue growth;
* :class:`HedgePolicy` — straggler cut-off by speculative re-issue on
  idle ranks, cancel-priced into the timeline's ``shed`` phase;
* :class:`CircuitBreaker` / :class:`RankBreakers` — rolling-fault-rate
  rank quarantine with half-open probe-back-in;
* :class:`ClusterJournal` / :class:`SimulatedCrash` — the JSONL
  write-ahead log behind ``PimCluster(journal=...)`` kill-and-resume.

See ``PimCluster(admission=, shedding=, hedge=, breaker=, journal=)``
for the integration points, and
``benchmarks/overload.py`` for the chaos sweeps that gate them.
"""
from repro.admission.breaker import CircuitBreaker, RankBreakers
from repro.admission.control import AdmissionPolicy, TokenBucket
from repro.admission.hedge import HedgePolicy
from repro.admission.journal import (JOURNAL_VERSION, ClusterJournal,
                                     SimulatedCrash)

__all__ = [
    "AdmissionPolicy", "TokenBucket", "HedgePolicy",
    "CircuitBreaker", "RankBreakers", "ClusterJournal", "SimulatedCrash",
    "JOURNAL_VERSION",
]
