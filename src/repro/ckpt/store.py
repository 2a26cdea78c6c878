"""Sharded, manifest-atomic checkpoints of pytrees.

Layout:
    <dir>/step_<N>.tmp/            (written first)
        shard_<p>.npz              (one per host process)
        manifest.json              (treedef paths, shapes, dtypes, step)
    <dir>/step_<N>/                (atomic rename commits)
    <dir>/LATEST                   (text file, updated last)

Leaves are saved and restored as host arrays.  ``repro.faults.remap``
checkpoints a launch's inputs here so that lost shards re-execute from
storage."""
from __future__ import annotations

import json
import os
import shutil
from typing import Any, Dict, Optional

import jax
import numpy as np


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for kp, leaf in flat:
        key = "/".join(
            str(k.key) if isinstance(k, jax.tree_util.DictKey) else str(k)
            for k in kp)
        out[key] = leaf
    return out, treedef


def save(ckpt_dir: str, step: int, tree: Dict[str, Any],
         process_index: int = 0, process_count: int = 1):
    """Save a pytree (arrays gathered to host)."""
    os.makedirs(ckpt_dir, exist_ok=True)
    tmp = os.path.join(ckpt_dir, f"step_{step}.tmp")
    final = os.path.join(ckpt_dir, f"step_{step}")
    if process_index == 0:
        shutil.rmtree(tmp, ignore_errors=True)
        shutil.rmtree(final, ignore_errors=True)
        os.makedirs(tmp)
    flat, _ = _flatten(tree)
    host = {k: np.asarray(v) for k, v in flat.items()}
    np.savez(os.path.join(tmp, f"shard_{process_index}.npz"), **host)
    if process_index == 0:
        manifest = {
            "step": step,
            "process_count": process_count,
            "leaves": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                       for k, v in host.items()},
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        os.replace(tmp, final) if not os.path.exists(final) else None
        if os.path.exists(tmp):
            shutil.rmtree(tmp, ignore_errors=True)
        with open(os.path.join(ckpt_dir, "LATEST.tmp"), "w") as f:
            f.write(str(step))
        os.replace(os.path.join(ckpt_dir, "LATEST.tmp"),
                   os.path.join(ckpt_dir, "LATEST"))
    return final


def latest_step(ckpt_dir: str) -> Optional[int]:
    p = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return int(f.read().strip())


def restore(ckpt_dir: str, like, step: Optional[int] = None):
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    data = {}
    for p in range(manifest["process_count"]):
        with np.load(os.path.join(d, f"shard_{p}.npz")) as z:
            for k in z.files:
                data[k] = z[k]

    flat_like, treedef = _flatten(like)
    leaves = []
    for key, leaf in flat_like.items():
        if key not in data:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = data[key]
        want_shape = tuple(np.shape(leaf))
        if tuple(arr.shape) != want_shape:
            raise ValueError(f"{key}: shape {arr.shape} != {want_shape}")
        if np.ndim(leaf) == 0 and not hasattr(leaf, "dtype"):
            arr = arr.item()  # python scalar leaf (e.g. iterator step)
        leaves.append(arr)
    # rebuild via unflatten on the like treedef (order matches flatten)
    _, td = jax.tree_util.tree_flatten(like)
    return jax.tree_util.tree_unflatten(td, leaves), step
