"""Jitted wrapper for the flash-attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bk",
                                    "interpret"))
def flash_attention_op(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                       interpret=False):
    return flash_attention(q, k, v, causal=causal, window=window, bq=bq,
                           bk=bk, interpret=interpret)
