"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.alu_exec.ops import alu_exec
from repro.kernels.alu_exec.ref import alu_exec_ref
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.ops import ssd_scan_op
from repro.kernels.ssd_scan.ref import ssd_chunk_ref


# ---------------------------------------------------------------------------
# alu_exec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 7, 1024, 1025, 4096])
def test_alu_kernel_shapes(n):
    rng = np.random.default_rng(n)
    op = jnp.asarray(rng.integers(0, 12, n), jnp.int32)
    a = jnp.asarray(rng.integers(-2**31, 2**31 - 1, n, dtype=np.int64)
                    .astype(np.int32))
    b = jnp.asarray(rng.integers(-2**31, 2**31 - 1, n, dtype=np.int64)
                    .astype(np.int32))
    assert (alu_exec(op, a, b, interpret=True) == alu_exec_ref(op, a, b)).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 11), st.integers(-2**31, 2**31 - 1),
       st.integers(-2**31, 2**31 - 1))
def test_alu_kernel_hypothesis(op, a, b):
    opv = jnp.full((8,), op, jnp.int32)
    av = jnp.full((8,), a, jnp.int32)
    bv = jnp.full((8,), b, jnp.int32)
    assert (alu_exec(opv, av, bv, interpret=True) == alu_exec_ref(opv, av, bv)).all()


def test_alu_edge_cases():
    cases = [(9, -2**31, -1), (9, 5, 0), (5, 1, 33), (7, -8, 1),
             (8, 2**30, 2)]
    op, a, b = map(lambda t: jnp.asarray(t, jnp.int32), zip(*cases))
    assert (alu_exec(op, a, b, interpret=True) == alu_exec_ref(op, a, b)).all()


def test_alu_nonalu_opcodes_return_zero():
    """Decode streams carry non-ALU opcodes (LW=12..SPC=30); the kernel
    must keep the oracle's 0-for-those contract (no downstream mask)."""
    op = jnp.asarray([12, 16, 28, 30, -1], jnp.int32)
    a = jnp.asarray([5, 6, 7, 8, 9], jnp.int32)
    b = jnp.asarray([1, 2, 3, 4, 5], jnp.int32)
    got = alu_exec(op, a, b, interpret=True)
    assert (got == alu_exec_ref(op, a, b)).all()
    assert (got == 0).all()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,h,kv,dk,dv,causal,window", [
    (128, 4, 4, 32, 32, True, 0),
    (128, 8, 2, 16, 16, True, 0),     # GQA
    (256, 4, 1, 32, 64, True, 0),     # MQA + Dv != Dk
    (128, 4, 4, 32, 32, False, 0),    # bidirectional (encoder)
    (256, 4, 2, 32, 32, True, 64),    # local window
])
def test_flash_kernel_vs_ref(s, h, kv, dk, dv, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, s, h, dk), jnp.float32)
    k = jax.random.normal(ks[1], (2, s, kv, dk), jnp.float32)
    v = jax.random.normal(ks[2], (2, s, kv, dv), jnp.float32)
    got = flash_attention_op(q, k, v, causal=causal, window=window,
                             bq=64, bk=64, interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_kernel_bf16():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 4, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 4, 32), jnp.bfloat16)
    got = flash_attention_op(q, k, v, bq=64, bk=64,
                             interpret=True).astype(jnp.float32)
    want = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_flash_matches_model_blocked_attention():
    """Kernel == the model's pure-jnp blocked path (the pair must agree)."""
    from repro.models.attention import blocked_attention
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 256, 8, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 2, 32), jnp.float32)
    a = flash_attention_op(q, k, v, bq=64, bk=64, interpret=True)
    b = blocked_attention(q, k, v, q_chunk=128, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,p,n,chunk", [
    (64, 8, 8, 16), (128, 16, 8, 32), (128, 32, 16, 64), (96, 8, 8, 96),
])
def test_ssd_kernel_vs_sequential_ref(s, p, n, chunk):
    bh = 3
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (bh, s, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, s)))
    A = -jnp.exp(jax.random.normal(ks[2], (bh,)))
    Bm = jax.random.normal(ks[3], (bh, s, n))
    Cm = jax.random.normal(ks[4], (bh, s, n))
    y, state = ssd_scan_op(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    for h in range(bh):
        yw, sw = ssd_chunk_ref(x[h], dt[h], A[h], Bm[h], Cm[h],
                               jnp.zeros((n, p)))
        np.testing.assert_allclose(np.asarray(y[h]), np.asarray(yw),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(state[h]), np.asarray(sw),
                                   rtol=2e-4, atol=2e-4)


def test_ssd_kernel_matches_model_path():
    """Kernel == repro.models.ssm.ssd_chunked (heads-batched layout)."""
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N = 2, 64, 3, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, H, N))
    Cm = jax.random.normal(ks[4], (B, S, H, N))
    y_model, st_model = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    # kernel layout: (B*H, S, ...)
    xk = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtk = dt.transpose(0, 2, 1).reshape(B * H, S)
    Ak = jnp.tile(A, B)
    Bk = Bm.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    Ck = Cm.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    y_k, st_k = ssd_scan_op(xk, dtk, Ak, Bk, Ck, chunk=16, interpret=True)
    y_k = y_k.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    st_k = st_k.reshape(B, H, N, P)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_k),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_model), np.asarray(st_k),
                               rtol=2e-4, atol=2e-4)
