"""Jitted wrapper: full-sequence SSD scan built from the chunk kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ssd_scan import ssd_chunk


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_op(x, dt, A, Bm, Cm, *, chunk=64, interpret=False):
    """Full sequence scan.  x: (BH, S, P)  dt: (BH, S)  A: (BH,)
    Bm/Cm: (BH, S, N) -> (y (BH, S, P), final_state (BH, N, P))."""
    BH, S, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    def to_chunks(t):
        return t.reshape(BH, nc, Q, *t.shape[2:]).transpose(
            1, 0, 2, *range(3, t.ndim + 1))

    xc, dtc, bc, cc = map(to_chunks, (x, dt, Bm, Cm))

    def step(state, inp):
        xq, dq, bq, cq = inp
        y, state = ssd_chunk(xq, dq, A, bq, cq, state, interpret=interpret)
        return state, y

    state0 = jnp.zeros((BH, N, P), jnp.float32)
    state, yc = jax.lax.scan(step, state0, (xc, dtc, bc, cc))
    y = yc.transpose(1, 0, 2, 3).reshape(BH, S, P)
    return y, state
