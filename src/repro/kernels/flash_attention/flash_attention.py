"""Pallas TPU kernel: blockwise (flash) causal attention with GQA.

HBM->VMEM staging discipline mirrors the paper's MRAM->WRAM DMA model:
each grid step holds one (bq, Dk) query tile plus streamed (bk, Dk) KV
tiles in VMEM, with the online-softmax running statistics in VREGs.
Causality is exploited structurally: the fori upper bound is qi+1 blocks,
so no masked-out KV block is ever fetched or multiplied (unlike the
pure-jnp training path, which must use static trip counts for reverse-mode
autodiff — see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, causal, window,
                  scale):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, Dk)
    S = k_ref.shape[2]
    Dv = v_ref.shape[3]
    nk = S // bk

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), bool)
        if causal:
            ok = kpos <= qpos
        if window > 0:
            ok = ok & (qpos - kpos < window)
        s = jnp.where(ok, s, NEG)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        ub = jnp.minimum((qi + 1) * bq // bk + ((qi + 1) * bq % bk != 0), nk)
    else:
        ub = nk
    lo = 0
    if window > 0:
        lo = jnp.maximum(qi * bq // bk - (-(-window // bk)), 0)
    m0 = jnp.full((bq,), NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, Dv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, ub, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                    interpret=False):
    """q: (B,S,H,Dk)  k: (B,S,KV,Dk)  v: (B,S,KV,Dv) -> (B,S,H,Dv)."""
    B, S, H, Dk = q.shape
    KV = k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    # layout: heads as leading grid dims so each (b, h) owns its KV head
    qt = q.transpose(0, 2, 1, 3)  # (B, H, S, Dk)
    kt = k.transpose(0, 2, 1, 3)  # (B, KV, S, Dk)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, H, S // bq)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, scale=Dk ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dk), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, Dk), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, S, Dv), lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
