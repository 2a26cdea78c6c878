"""The scalar engine's step writes narrow per-lane state without scatters.

XLA lowers a scatter with dynamic indices on the TPU to a serial loop
over the updated indices, so each costs about the same whatever the
array's size.  The step therefore writes every state axis of at most
``engine.ONEHOT_MAX`` entries (tasklet latches, registers, mutexes,
counters, the TLP series, the D$ and TLB) as a dense one-hot select.
Only the wide WRAM/MRAM word axes keep their scatters: the WRAM store
and the DMA copies, five per issue slot.
"""
import re

import jax
import numpy as np
import pytest

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
    """Shapes of the arrays the lowered step scatters into."""
    st = engine.make_state_np(cfg, None, np.zeros((D, 1), np.int32),
                              np.zeros((D, cfg.mram_words), np.int32), T)
    spec = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
    ir = tuple(jax.ShapeDtypeStruct((cfg.iram_instrs,), np.int32)
               for _ in range(6))
    text = jax.jit(engine.make_step_traced(cfg)).lower(ir, spec).as_text()
    return [tuple(int(n) for n in m.split("x"))
            for m in _SCATTER.findall(text)]


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_no_scatter_on_narrow_axes(variant):
    cfg = DPUConfig(n_dpus=D, n_tasklets=T, mram_bytes=1 << 16,
                    **VARIANTS[variant])
    targets = _scatter_targets(cfg)
    narrow = [s for s in targets if max(s[1:]) <= engine.ONEHOT_MAX]
    assert narrow == []
    # the WRAM store and the four DMA-copy scatters of each issue slot
    assert len(targets) == 5 * cfg.superscalar
