"""The scalar engine's step writes narrow per-lane state without scatters.

XLA lowers a scatter with dynamic indices on the TPU to a serial loop
over the updated indices, so each costs about the same whatever the
array's size.  The step therefore writes every state axis of at most
``engine.ONEHOT_MAX`` entries (tasklet latches, registers, mutexes,
counters, the TLP series, the D$ and TLB) as a dense one-hot select.
Only the wide WRAM/MRAM word axes keep their scatters: the WRAM store
and the DMA copies, five per issue slot.  Past
``engine.FLAT_CARRY_WORDS`` of WRAM the step takes WRAM and MRAM flat
(``engine.to_carry``), and those five scatter into the 1-D ``[D * W]``
WRAM and ``[D * M]`` MRAM.
"""
import re

import jax
import numpy as np
import pytest

from repro.core import backend as backends
from repro.core import engine
from repro.core.config import DPUConfig

D, T = 64, 16

VARIANTS = {
    "default": {},
    "forwarding": {"forwarding": True},
    "superscalar2": {"superscalar": 2},
    "cache_mode": {"cache_mode": True},
    "mmu": {"mmu": True},
    "no_detail": {"collect_detail": False},
}

# a scatter's result type closes its multi-line op: `}) : (...) -> tensor<...>`
_SCATTER = re.compile(r'"stablehlo\.scatter".*?\}\) : \(.*?\) -> tensor<([0-9x]+)x\w+>',
                      re.S)


def _scatter_targets(cfg: DPUConfig):
    """Shapes of the arrays the step, lowered over the scalar backend's
    carry form of a ``cfg.n_dpus``-lane state, scatters into."""
    one = engine.make_state_np(cfg.replace(n_dpus=1), None,
                               np.zeros((1, 1), np.int32),
                               np.zeros((1, cfg.mram_words), np.int32), T)
    spec = jax.eval_shape(backends.get("scalar").to_carry, {
        k: jax.ShapeDtypeStruct((cfg.n_dpus,) + x.shape[1:], x.dtype)
        for k, x in one.items()})
    ir = tuple(jax.ShapeDtypeStruct((cfg.iram_instrs,), np.int32)
               for _ in range(6))
    text = jax.jit(engine.make_step_traced(cfg)).lower(ir, spec).as_text()
    return [tuple(int(n) for n in m.split("x"))
            for m in _SCATTER.findall(text)]


def _check_scatters(variant, lanes, flat):
    # MRAM twice WRAM's size, so the two wide targets tell apart
    cfg = DPUConfig(n_dpus=lanes, n_tasklets=T, mram_bytes=1 << 17,
                    **VARIANTS[variant])
    assert (lanes * cfg.wram_words > engine.FLAT_CARRY_WORDS) == flat
    targets = _scatter_targets(cfg)
    narrow = [s for s in targets if np.prod(s) // lanes <= engine.ONEHOT_MAX]
    assert narrow == []
    # the WRAM store and the four DMA-copy scatters of each issue slot,
    # each into the whole WRAM or MRAM
    wram, mram = (lanes, cfg.wram_words), (lanes, cfg.mram_words)
    if flat:
        wram, mram = (np.prod(wram),), (np.prod(mram),)
    assert sorted(targets) == sorted([wram] * 3 * cfg.superscalar
                                     + [mram] * 2 * cfg.superscalar)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_no_scatter_on_narrow_axes(variant):
    """One rank: WRAM and MRAM stay ``[D, W]`` / ``[D, M]``."""
    _check_scatters(variant, D, flat=False)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_flat_carry_scatters_into_flat_memories(variant):
    """1,024 lanes: past ``engine.FLAT_CARRY_WORDS`` the wide scatters
    write the flat ``[D * W]`` WRAM and ``[D * M]`` MRAM."""
    _check_scatters(variant, 1024, flat=True)
