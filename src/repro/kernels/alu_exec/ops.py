"""Jitted wrapper: pad/reshape a flat int32 stream through the ALU kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.alu_exec.alu_exec import TILE, alu_exec_2d

_LANE = TILE[0] * TILE[1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def alu_exec(op, a, b, *, interpret=False):
    """Flat (N,) int32 op/a/b -> (N,) int32 results via the Pallas kernel."""
    n = op.shape[0]
    pad = (-n) % _LANE
    op_p = jnp.pad(op, (0, pad)).reshape(-1, TILE[1])
    a_p = jnp.pad(a, (0, pad)).reshape(-1, TILE[1])
    b_p = jnp.pad(b, (0, pad)).reshape(-1, TILE[1])
    out = alu_exec_2d(op_p, a_p, b_p, interpret=interpret)
    return out.reshape(-1)[:n]
