"""The comparison that decides ``correct``.

Each simulation of the window is held to references that share no code
with the program, once the window has closed:

* its output: the leading MRAM words of every DPU after the run, against
  the plain reference of the workload (``reference/<workload>.py``),
  which draws the same inputs from the data seed and computes the answer
  with numpy alone;
* its timing: for a sample of the window's simulations and of their
  DPUs, drawn from the run seed, each launch's cycles and issued
  instructions on that DPU, against the plain DPU model
  (``reference/dpu.py``) running the cell's kernel from the inputs the
  workload's reference gives for that launch.  The DPU that the program
  reports slowest in each launch is always in the sample;
* its simulated statistics (cycles, issued instructions, launches and
  the timeline's phase totals), against the values pinned for its data
  seed in ``expected/<cell>.json``: a regression check of the host's
  pricing beside the DPU model.

Every comparison is exact: every number compared has the limit 0.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from pimbench.reference import dpu as dpu_model

LIMITS = {"failed_simulations": 0, "output_words_wrong": 0,
          "timing_wrong": 0, "statistics_wrong": 0}
#: simulations of a run, and DPUs of each launch, held to the DPU model
SAMPLE_SIMS, SAMPLE_DPUS = 2, 3


def words_wrong(got, want: np.ndarray) -> int:
    """MRAM words that differ; a missing or misshapen image is all wrong."""
    if got is None or got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))


def stats_wrong(got, want: dict) -> int:
    """Pinned statistics that differ, or all of them when none came."""
    if got is None:
        return len(want)
    return sum(got.get(k) != v for k, v in want.items())


def timing_wrong(cell, sim, rng: np.random.Generator) -> int:
    """Launches missing or extra, and sampled (launch, DPU) pairs whose
    cycles or issued instructions differ from the plain DPU model."""
    dpu, t = cell.dpu(), int(cell.dpu()["n_tasklets"])
    kernel = dpu_model.kernel(f"{cell.traffic['workload']}.t{t}")
    want = cell.reference().launches(dpu, cell.traffic["sizes"],
                                     sim.data_seed)
    wrong = abs(len(want) - len(sim.launches))
    d = int(dpu["n_dpus"])
    for (args, mram), got in zip(want, sim.launches):
        sample = set(rng.choice(d, min(SAMPLE_DPUS, d), replace=False)
                     .tolist())
        sample.add(int(np.argmax(got.cycles)))
        for k in sorted(sample):
            ref = dpu_model.run(kernel, args[k], mram[k].tolist(), dpu=dpu,
                                tasklets=t, dpu_id=k, n_dpus=d)
            wrong += ref != (int(got.cycles[k]), int(got.issued[k]))
    return wrong


def judge(cell, sims: List, expected: Dict[str, dict], seed: int) -> dict:
    ref = cell.reference()
    dpu, sizes = cell.dpu(), cell.traffic["sizes"]
    out = dict.fromkeys(LIMITS, 0)
    for s in sims:
        out["failed_simulations"] += s.error is not None
        out["output_words_wrong"] += words_wrong(
            s.image, ref.image(dpu, sizes, s.data_seed))
        want = expected.get(str(s.data_seed))
        # a data seed with no pinned statistics cannot be shown right
        out["statistics_wrong"] += (stats_wrong(s.stats, want)
                                    if want is not None else 1)
    rng = np.random.default_rng(int(seed) % (1 << 64))
    for k in sorted(rng.choice(len(sims), min(SAMPLE_SIMS, len(sims)),
                               replace=False).tolist()):
        out["timing_wrong"] += timing_wrong(cell, sims[k], rng)
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in out.items()}


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
