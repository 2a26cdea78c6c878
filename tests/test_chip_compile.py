"""Compile the simulator's engine for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and raises what the chip's compiler would raise (tiling, VMEM,
device-memory limits).  Nothing runs, so these tests say nothing about
results or speed.  The topology is described inside a fixture, never at
import, so test collection stays identical across pytest-xdist workers.
"""
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.workloads as wl
from repro.core import backend as backends
from repro.core import compile_cache, hbmpim
from repro.core.config import DPUConfig
from repro.workloads.linalg import GEMV_C

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A described-device compile cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_engine(cfg, binary, mram_words, n_threads, sharding):
    """Compile the compile cache's while-loop driver exactly as a launch of
    ``binary`` on ``cfg`` would build it (same program and DPU buckets),
    from shapes alone."""
    be = backends.get(backends.resolve_backend(cfg))
    P = compile_cache.program_bucket(binary.n_instrs, binary.opcode.shape[0])
    Dp = compile_cache.dpu_bucket(cfg.n_dpus)
    # one DPU's state gives every leaf's trailing shape; the leading axis
    # is the DPU bucket
    one = be.make_state(cfg.replace(n_dpus=1), binary,
                        np.zeros((1, 8), np.int32),
                        np.zeros((1, mram_words), np.int32), n_threads)
    assert all(x.shape[0] == 1 for x in jax.tree_util.tree_leaves(one))
    st = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((Dp,) + x.shape[1:], x.dtype), one)
    # the device takes the state in the backend's carry form
    st = jax.tree_util.tree_map(lambda x: _spec(x.shape, x.dtype, sharding),
                                jax.eval_shape(be.to_carry, st))
    ir = tuple(_spec((P,), a.dtype, sharding) for a in binary.arrays)
    go = compile_cache._make_go(cfg, be, n_threads)
    compiled = go.lower(ir, st).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES
    return compiled


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_RELAYOUT = re.compile(r"= \w+\[([\d,]*)\]\S* (?:copy|reshape|transpose)\(")


def _loop_relayouts(hlo: str, sizes) -> list:
    """Copies, reshapes and transposes of ``sizes`` elements in the
    compiled HLO's ``while`` bodies and conditions and every computation
    they call (conditional branches, fusions), as HLO lines."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    todo = re.findall(r"(?:body|condition)=%([\w.\-]+)", hlo)
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += [r for line in comps[c]
                 for r in re.findall(r"%([\w.\-]+)", line) if r in comps]
    return [line.strip() for c in seen for line in comps[c]
            for m in [_RELAYOUT.search(line)]
            if m and int(np.prod([int(n) for n in m.group(1).split(",")
                                  if n])) in sizes]


def _va(cfg, n_threads=16):
    return wl.get("VA").build(n_threads).binary(cfg.iram_instrs)


@pytest.mark.parametrize("backend,mram_bytes", [
    ("scalar", 1 << 18),
    ("simt", 1 << 18),
])
def test_engine_compiles_one_rank(one_chip, backend, mram_bytes):
    kw = {"simt_width": 4} if backend == "simt" else {}
    cfg = DPUConfig(n_dpus=64, n_tasklets=16, mram_bytes=mram_bytes, **kw)
    _compile_engine(cfg, _va(cfg), cfg.mram_words, 16, one_chip)


def test_hbmpim_cmd_gemvs_compiles(one_chip):
    cfg = DPUConfig(n_dpus=64, backend="hbmpim_cmd", mram_bytes=1 << 20)
    G = wl.get("GEMVS").n_elems(1.0) // cfg.hbm_lanes
    p = hbmpim.CrfProgram()
    for i in range(8):
        for g in range(G):
            p.mac(hbmpim.bank(GEMV_C * G + g), hbmpim.bank(i * G + g),
                  hbmpim.srf(i))
    p.exit_()
    _compile_engine(cfg, p.binary(cfg.hbm_crf_slots), cfg.mram_words, 1,
                    one_chip)


def test_engine_compiles_full_server(one_chip):
    """2,560 DPUs (40 ranks x 64) run on a 2,560-lane bucket, a multiple
    of the 128-lane tile, with no padded lane.  Past
    ``engine.FLAT_CARRY_WORDS`` the engine keeps WRAM and MRAM flat
    through its loop: no WRAM- or MRAM-sized relayout in it."""
    cfg = DPUConfig(n_dpus=2560, n_ranks=40, n_tasklets=16,
                    mram_bytes=1 << 16)
    assert compile_cache.dpu_bucket(cfg.n_dpus) == 2560
    hlo = _compile_engine(cfg, _va(cfg), cfg.mram_words, 16,
                          one_chip).as_text()
    assert re.search(r"body=%", hlo)
    assert _loop_relayouts(hlo, {2560 * cfg.wram_words,
                                 2560 * cfg.mram_words}) == []
