"""Pallas TPU kernel: one SSD (Mamba-2 state-space-duality) chunk.

Implements the chunked dual form for a (Q, P) chunk of one head entirely
in VMEM: the quadratic intra-chunk term (a masked (Q, Q) matmul on the
MXU), the inter-chunk term from the incoming state, and the state update —
the three einsums of DESIGN.md §3 fused into one kernel so the (Q, Q)
decay matrix never leaves VMEM.  The grid runs over (batch x heads);
the host-side ``lax.scan`` carries the state across chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s_ref, y_ref, so_ref):
    x = x_ref[0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)      # (Q, 1)
    A = a_ref[0].astype(jnp.float32)        # (1, 1) negative
    Bm = b_ref[0].astype(jnp.float32)       # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)       # (Q, N)
    s0 = s_ref[0].astype(jnp.float32)       # (N, P)
    Q = x.shape[0]

    # Mosaic has no cumsum and no 1-D layouts: every vector stays a
    # (Q, 1) column or a (1, Q) row, and prefix sums are masked (Q, Q)
    # reductions
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)

    def row(col):                            # (Q, 1) -> (1, Q)
        return jnp.sum(jnp.where(qi == ki, col, 0.0), axis=0, keepdims=True)

    dA = dt * A                              # (Q, 1)
    dt_r, dA_r = row(dt), row(dA)            # (1, Q)
    seg = jnp.sum(jnp.where(ki <= qi, dA_r, 0.0), axis=1, keepdims=True)
    seg_r = jnp.sum(jnp.where(qi <= ki, dA, 0.0), axis=0, keepdims=True)
    total = jnp.sum(dA, axis=0, keepdims=True)   # (1, 1) = seg[Q - 1]

    # inter-chunk: y_inter = (C * exp(seg)) @ s0
    y_inter = jax.lax.dot_general(
        Cm * jnp.exp(seg), s0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # intra-chunk: masked (Q, Q) attention-like term
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    decay = jnp.exp(seg - seg_r)
    w = jnp.where(qi >= ki, cb * decay * dt_r, 0.0)
    y_intra = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # state update
    wk = jnp.exp(total - seg) * dt           # (Q, 1)
    s_out = s0 * jnp.exp(total) + jax.lax.dot_general(
        Bm * wk, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0] = (y_inter + y_intra).astype(y_ref.dtype)
    so_ref[0] = s_out.astype(so_ref.dtype)


def ssd_chunk(x, dt, A, Bm, Cm, state0, *, interpret=False):
    """Batched single-chunk SSD.

    x: (BH, Q, P)  dt: (BH, Q)  A: (BH,)  Bm/Cm: (BH, Q, N)
    state0: (BH, N, P)  ->  (y (BH, Q, P), state_out (BH, N, P))."""
    BH, Q, P = x.shape
    N = Bm.shape[-1]
    grid = (BH,)
    y, so = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, Q, 1), lambda i: (i, 0, 0)),
            # (1, 1, 1) spans A's two minor dims whole, as TPU tiling needs
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, N, P), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, N, P), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt[..., None], A[:, None, None], Bm, Cm, state0)
    return y, so
