"""Checkpointing + fault-tolerance runtime tests (injected failures)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import store
from repro.runtime.coordinator import (StepMonitor, WorkerFailure,
                                       WorkRebalancer, run_with_restarts)


def _mk_state(seed=0):
    """A small mixed pytree: float and int leaves, nested dicts, a
    scalar counter."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = {"w": jax.random.normal(k1, (3, 4), jnp.float32),
              "b": jax.random.normal(k2, (4,), jnp.float32)}
    return {"params": params,
            "momentum": jax.tree_util.tree_map(jnp.zeros_like, params),
            "count": jnp.int32(0),
            "mask": jnp.asarray(np.arange(6, dtype=np.int32) % 2)}


class _Batches:
    """Deterministic batches indexed by step; ``step`` is the iterator
    state that a checkpoint carries."""

    def __init__(self, seed=0):
        self.seed, self.step = seed, 0

    def batch_at(self, i):
        rng = np.random.default_rng((self.seed, i))
        x = rng.standard_normal((8, 3)).astype(np.float32)
        return x, rng.standard_normal((8, 4)).astype(np.float32)

    def state_dict(self):
        return {"step": self.step}

    def load_state_dict(self, s):
        self.step = int(s["step"])


@jax.jit
def _sgd_step(state, x, y):
    """One momentum-SGD step of a linear least-squares fit."""
    def loss(p):
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)
    g = jax.grad(loss)(state["params"])
    mom = jax.tree_util.tree_map(lambda m, d: 0.9 * m + d,
                                 state["momentum"], g)
    params = jax.tree_util.tree_map(lambda p, m: p - 0.05 * m,
                                    state["params"], mom)
    return {**state, "params": params, "momentum": mom,
            "count": state["count"] + 1}


def test_ckpt_roundtrip(tmp_path):
    state = _mk_state()
    store.save(str(tmp_path), 7, state)
    assert store.latest_step(str(tmp_path)) == 7
    restored, step = store.restore(str(tmp_path), state)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ckpt_latest_wins(tmp_path):
    state = _mk_state()
    store.save(str(tmp_path), 1, state)
    store.save(str(tmp_path), 5, state)
    assert store.latest_step(str(tmp_path)) == 5


def test_ckpt_missing_leaf_raises(tmp_path):
    state = _mk_state()
    store.save(str(tmp_path), 1, {"params": state["params"]})
    with pytest.raises(KeyError):
        store.restore(str(tmp_path), state)


def test_restart_driver_survives_failures(tmp_path):
    """A step loop with injected step failures completes and matches the
    failure-free trajectory (exact replay from checkpoints)."""
    state0 = _mk_state()

    def run(inject):
        data = _Batches()
        ref = {"state": jax.tree_util.tree_map(jnp.copy, state0)}
        fail_at = {3, 7} if inject else set()
        seen = set()

        def one_step(i):
            if inject and i in fail_at and i not in seen:
                seen.add(i)
                raise WorkerFailure(f"node died at step {i}")
            ref["state"] = _sgd_step(ref["state"], *data.batch_at(i))
            data.step = i + 1

        stats = run_with_restarts(
            one_step, state_ref=ref, data=data, n_steps=10,
            ckpt_dir=str(tmp_path / ("f" if inject else "c")), ckpt_every=2)
        return ref["state"], stats

    s_clean, st_clean = run(False)
    s_fail, st_fail = run(True)
    assert st_fail["failures"] == 2 and st_fail["restores"] == 2
    assert st_clean["completed"] == st_fail["completed"] == 10
    assert int(s_clean["count"]) == int(s_fail["count"]) == 10
    for a, b in zip(jax.tree_util.tree_leaves(s_clean["params"]),
                    jax.tree_util.tree_leaves(s_fail["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_step_monitor_detects():
    m = StepMonitor(deadline_factor=5.0, straggler_factor=1.5)
    for _ in range(5):
        assert m.observe(1.0) == "ok"
    assert m.observe(2.0) == "straggler"
    assert m.observe(10.0) == "failed"


def test_rebalancer_beats_naive():
    """Greedy LPT with observed rates beats contiguous assignment when one
    worker is 4x slow (the straggler-mitigation path)."""
    rng = np.random.default_rng(0)
    costs = rng.uniform(1, 5, 64)
    rates = np.array([1.0, 1.0, 1.0, 0.25])  # worker 3 is the straggler
    rb = WorkRebalancer(4)
    smart = rb.assign(costs, rates)
    naive = [list(range(i * 16, (i + 1) * 16)) for i in range(4)]
    assert rb.makespan(smart, costs, rates) < 0.5 * rb.makespan(
        naive, costs, rates)
