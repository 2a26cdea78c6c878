"""Pallas TPU kernel: the simulator's decode/execute ALU datapath.

The per-cycle hot loop of the vectorized DPU engine is a 12-way opcode
switch over (DPU,) int32 vectors.  On TPU this runs on the VPU over
(8, 128)-tiled int32 registers held in VMEM — the kernel is the
TPU-native analogue of the C++ interpreter's switch statement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.engine import select_tree

TILE = (8, 128)


def _alu_kernel(op_ref, a_ref, b_ref, o_ref):
    op = op_ref[...]
    a = a_ref[...]
    b = b_ref[...]
    sh = b.astype(jnp.uint32) & 31
    au = a.astype(jnp.uint32)
    bu = b.astype(jnp.uint32)
    safe_b = jnp.where(b == 0, 1, b)
    results = [
        a + b,
        a - b,
        a & b,
        a | b,
        a ^ b,
        (au << sh).astype(jnp.int32),
        (au >> sh).astype(jnp.int32),
        a >> sh.astype(jnp.int32),
        a * b,
        jnp.where(b == 0, -1, jax.lax.div(a, safe_b)),
        (a < b).astype(jnp.int32),
        (au < bu).astype(jnp.int32),
    ]
    # balanced select tree (mirrors engine.alu_exec): log2(12) select
    # depth on the VPU instead of a 12-long dependent where chain.
    # Unlike the engine (whose caller masks on op <= SLTU), this kernel
    # has no downstream mask, so keep the oracle's 0-for-non-ALU-opcode
    # contract explicitly (the decode stream carries ops up to SPC=30).
    out = select_tree(op, results)
    o_ref[...] = jnp.where((op >= 0) & (op < len(results)), out, 0)


def alu_exec_2d(op, a, b, *, interpret=False):
    """op/a/b: (R, 128) int32 with R a multiple of 8."""
    R = op.shape[0]
    assert op.shape == a.shape == b.shape and op.shape[1] == TILE[1]
    assert R % TILE[0] == 0
    grid = (R // TILE[0],)
    spec = pl.BlockSpec(TILE, lambda i: (i, 0))
    return pl.pallas_call(
        _alu_kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(op.shape, jnp.int32),
        interpret=interpret,
    )(op, a, b)
