"""Pinned simulated statistics every platform must reproduce bit for bit.

The numbers were captured before the ``ExecBackend`` extraction and have
held on every engine change since.  ``tests/test_backend.py`` checks them
on the CPU and ``chip_smoke.py`` on the accelerator: a simulated
statistic never depends on the device that computed it."""
from __future__ import annotations

from repro.core.config import DPUConfig
from repro.core.host import PIMSystem

# "<workload>-<backend>" -> (cycles, issued, timeline.total, timeline.kernel)
GOLDENS = {
    "VA-scalar": (5336, 11488, 4.131521235521236e-05,
                  1.5245714285714286e-05),
    "VA-simt": (2133, 11488, 3.216378378378378e-05,
                6.094285714285714e-06),
    "BFS-scalar": (68900, 30916, 0.00027344401544401544,
                   0.00019685714285714285),
}


def golden_config(**kw) -> DPUConfig:
    """The pinned system: 4 DPUs over 2 ranks on 2 channels."""
    return DPUConfig(n_dpus=4, n_ranks=2, n_channels=2, **kw)


def run_golden(name: str) -> tuple:
    """Run one pinned case (8 threads, ``scale=0.02``, seed 0) through
    ``Workload.run``; returns the statistics in :data:`GOLDENS` order."""
    from repro.workloads import get
    wl_name, be = name.split("-")
    kw = {"simt_width": 4} if be == "simt" else {}
    system = PIMSystem(golden_config(**kw))
    _, rep = get(wl_name).run(system, 8, scale=0.02, seed=0)
    return rep.cycles, rep.issued, system.timeline.total, system.timeline.kernel
