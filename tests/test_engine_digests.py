"""Every leaf of the scalar engine's final state, pinned bit for bit.

Each case runs a PrIM kernel through ``Workload.run`` on a 64-DPU system
under one engine variant and digests every state leaf of every launch it
makes.  The pins in ``data/engine_digests.json`` were captured before the
step's per-lane state updates were rewritten as one-hot selects; any
change to how the step writes state must reproduce them exactly.
Regenerate only for a change that is meant to alter simulated results:
``PYTHONPATH=src python tests/test_engine_digests.py --write``.
"""
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import repro.workloads as wl
from repro.core import compile_cache, engine
from repro.core.asm import TID, ZERO, Program
from repro.core.config import DPUConfig
from repro.core.host import PIMSystem

PINS = Path(__file__).parent / "data" / "engine_digests.json"

# variant -> DPUConfig overrides
VARIANTS = {
    "default": {},
    "forwarding": {"forwarding": True},
    "superscalar2": {"superscalar": 2},
    "cache_mode": {"cache_mode": True, "wram_bytes": 1 << 18},
    "mmu": {"mmu": True},
    "no_detail": {"collect_detail": False},
}
# workload -> scale.  VA streams DMA; BFS runs one level per launch
# between barriers; SYNC (below) contends on mutexes.  Cache mode has no
# DMA and runs the cacheable kernels.
KERNELS = {"VA": 0.03, "BFS": 0.05, "SYNC": None}
CACHE_KERNELS = {"VA": 0.03, "BS": 0.05, "SYNC": None}
# The rest of PrIM, pinned by the ``test_prim_digests_*`` files against
# ``data/prim_digests.json``.
PRIM_KERNELS = {name: 0.03 for name in (
    "RED", "SCAN-SSA", "SCAN-RSS", "SEL", "UNI", "HST-S", "HST-L", "BS",
    "TS", "GEMV", "TRNS", "SpMV", "NW")}

CASES = [f"{k}/{v}" for v in VARIANTS
         for k in (CACHE_KERNELS if v == "cache_mode" else KERNELS)]


def run_sync(cfg: DPUConfig):
    """Mutex rounds on two locks, a barrier, then per-tasklet DMA and
    long ALU ops: every per-lane latch the step writes, in one launch."""
    nt = cfg.n_tasklets
    p = Program("sync", nt)
    cnt = p.walloc("cnt", 8)
    buf = p.walloc("buf", 64 * nt)
    v, i, w, m = p.regs("v", "i", "w", "m")
    for lock in (0, 1):
        with p.for_range(i, 0, 3):
            p.acquire(lock)
            p.lw(v, ZERO, cnt + 4 * lock)
            p.add(v, v, TID)
            p.sw(ZERO, cnt + 4 * lock, v)
            p.release(lock)
    p.barrier()
    p.sll(w, TID, 6)
    p.add(w, w, buf)
    p.sll(m, TID, 7)
    p.ldma(w, m, 64)
    p.lw(v, w, 4)
    p.mul(v, v, TID)
    p.div(v, v, 3)
    p.sw(w, 0, v)
    p.add(m, m, 4096)
    p.sdma(w, m, 64)
    p.stop()
    mram = np.random.default_rng(0).integers(
        -1000, 1000, (cfg.n_dpus, cfg.mram_words)).astype(np.int32)
    engine.run(cfg, p.binary(cfg.iram_instrs),
               np.zeros((cfg.n_dpus, 1), np.int32), mram)


def digest_case(case: str, n_dpus: int = 64) -> dict:
    """Per-leaf sha256 prefix over the final state of every launch of
    ``case`` (``"<kernel>/<variant>"``), ``n_dpus`` DPUs, 16 tasklets,
    seed 0."""
    name, variant = case.split("/")
    kw = VARIANTS[variant]
    cache_mode = kw.get("cache_mode", False)
    cfg = DPUConfig(n_dpus=n_dpus, n_tasklets=16, mram_bytes=1 << 16, **kw)
    hashes = {}
    real_run = compile_cache.run

    def recording_run(*a, **k):
        out = real_run(*a, **k)
        for leaf, x in out.items():
            hashes.setdefault(leaf, hashlib.sha256()).update(
                np.ascontiguousarray(x).tobytes())
        return out

    compile_cache.run = recording_run
    try:
        if name == "SYNC":
            run_sync(cfg)
        else:
            scales = CACHE_KERNELS if cache_mode else {**PRIM_KERNELS,
                                                       **KERNELS}
            wl.get(name).run(PIMSystem(cfg), 16, seed=0, cache_mode=cache_mode,
                             scale=scales[name])
    finally:
        compile_cache.run = real_run
    return {leaf: h.hexdigest()[:16] for leaf, h in sorted(hashes.items())}


@pytest.mark.parametrize("case", CASES)
def test_final_state_digests(case):
    want = json.loads(PINS.read_text())[case]
    assert digest_case(case) == want


@pytest.mark.parametrize("case", CASES)
def test_final_state_digests_flat_carry(case, monkeypatch):
    """The same pins with WRAM and MRAM flat in the loop carry, as past
    ``engine.FLAT_CARRY_WORDS`` (cut to 0 here: 64 lanes hold less)."""
    monkeypatch.setattr(engine, "FLAT_CARRY_WORDS", 0)
    want = json.loads(PINS.read_text())[case]
    assert digest_case(case) == want


if __name__ == "__main__" and sys.argv[1:] == ["--write"]:
    PINS.write_text(json.dumps({c: digest_case(c) for c in CASES},
                               indent=1, sort_keys=True) + "\n")
