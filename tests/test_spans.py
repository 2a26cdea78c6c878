"""Host spans and engine counters: what a profiled simulation records,
and what ``compile_cache.stats()`` counts."""
import numpy as np
import pytest

import jax
import repro.workloads as wl
from repro.core import backend as backends
from repro.core import compile_cache
from repro.comm import collectives
from repro.core.asm import Program
from repro.core.config import DPUConfig
from repro.core.host import PIMSystem
from repro.obs import spans
from repro.workloads.base import Workload


def _profile(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``repro.*`` host spans it
    recorded, as ``(name, start, end, args)`` sorted by start."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    (path,) = tmp_path.rglob("*.xplane.pb")
    prof = jax.profiler.ProfileData.from_file(str(path))
    out = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
            dict(list(ev.stats)))
           for plane in prof.planes if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("repro.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _parent(s, recorded):
    """The innermost other span that covers ``s``."""
    cover = [p for p in recorded if p is not s and _inside(s, p)]
    return max(cover, key=lambda p: (p[1], -p[2]))[0] if cover else None


class _Tiny(Workload):
    """Two launches of a two-instruction kernel, each followed by an
    allreduce, between a host write and a host read."""

    name = "TINY"

    def build(self, n_tasklets, cache_mode=False):
        p = Program("tiny", n_tasklets)
        r = p.reg("r")
        p.add(r, r, 3)
        p.stop()
        return p

    def _run(self, system, n_threads, scale=1.0, seed=0, cache_mode=False):
        D = system.cfg.n_dpus
        binary = self.build(n_threads).binary(system.cfg.iram_instrs)
        mram = np.zeros((D, system.cfg.mram_words), np.int32)
        system.h2d(64)
        for _ in range(2):
            st, rep = system.launch("tiny", binary, np.zeros((D, 1), np.int32),
                                    mram, n_threads=n_threads)
            collectives.allreduce(system, np.array(st["mram"]), 0, 4)
        system.d2h(64)
        return st, rep


def test_profiled_simulation_records_nested_spans(tmp_path):
    cfg = DPUConfig(n_dpus=2, n_tasklets=2, mram_bytes=1 << 12)

    def simulate():
        _Tiny().run(PIMSystem(cfg), 2, seed=3)
        # a pipelined run's batches are one simulation, resolved by sync
        _Tiny().run(PIMSystem(cfg, mode="async"), 2, seed=5, pipeline=2)

    compile_cache.clear()
    recorded = _profile(tmp_path, simulate)
    sims = [s for s in recorded if s[0] == spans.SIM]
    assert [(s[3]["workload"], s[3]["seed"]) for s in sims] == \
        [("TINY", 3), ("TINY", 5)]
    assert sims[0][3]["sim_id"] != sims[1][3]["sim_id"]
    assert {s[0] for s in recorded} == {
        spans.SIM, spans.LAUNCH, spans.LAUNCH_PREPARE, spans.LAUNCH_UPLOAD,
        spans.LAUNCH_DEVICE, spans.LAUNCH_READBACK, spans.HOST_REPORT,
        spans.COMM_COLLECTIVE, spans.COMM_TRANSFER, spans.SCHED_SYNC}
    launch_parts = [spans.LAUNCH_PREPARE, spans.LAUNCH_UPLOAD,
                    spans.LAUNCH_DEVICE, spans.LAUNCH_READBACK]
    for s in recorded:
        parent = _parent(s, recorded)
        want = {spans.SIM: None, **dict.fromkeys(launch_parts, spans.LAUNCH)
                }.get(s[0], spans.SIM)
        assert parent == want, s
    for sim in sims:
        mine = [s for s in recorded if _inside(s, sim) and s is not sim]
        launches = [s for s in mine if s[0] == spans.LAUNCH]
        assert len(launches) == 2 * (1 + (sim is sims[1]))
        for la in launches:
            assert (la[3]["sim_id"], la[3]["backend"], la[3]["dpus"],
                    la[3]["lanes"]) == (sim[3]["sim_id"], "scalar", 2, 2)
            parts = [s[0] for s in mine if _inside(s, la) and s is not la]
            assert parts == launch_parts
    # the first launch built the executable that every later one reused
    caches = [s[3]["cache"] for s in recorded if s[0] == spans.LAUNCH]
    assert caches == ["miss"] + ["hit"] * 5
    assert {s[3]["kind"] for s in recorded
            if s[0] == spans.COMM_COLLECTIVE} == {"allreduce"}
    assert [s[3]["kind"] for s in recorded if s[0] == spans.COMM_TRANSFER
            and _inside(s, sims[0])] == ["H2D", "D2H"]
    assert [s for s in recorded if s[0] == spans.SCHED_SYNC
            and _inside(s, sims[1])]
    for s in recorded:
        if s[0] in (spans.LAUNCH_UPLOAD, spans.LAUNCH_READBACK):
            assert s[3]["nbytes"] > 0


def test_launch_outside_a_simulation_has_no_sim_id(tmp_path):
    cfg = DPUConfig(n_dpus=2, n_tasklets=2, mram_bytes=1 << 12)
    binary = _Tiny().build(2).binary(cfg.iram_instrs)
    recorded = _profile(
        tmp_path, lambda: PIMSystem(cfg).prewarm(binary, n_threads=2))
    (launch,) = [s for s in recorded if s[0] == spans.LAUNCH]
    assert launch[3]["sim_id"] == -1
    assert spans.sim_id() == -1


def _va(n_dpus=4, **kw):
    cfg = DPUConfig(n_dpus=n_dpus, n_tasklets=16, mram_bytes=1 << 16, **kw)
    W = wl.get("VA")
    hd = W.host_data(cfg, 0.02, 0)
    binary = W.build(8).binary(cfg.iram_instrs)
    wram = np.zeros((n_dpus, 16), np.int32)
    wram[:, :hd.args.shape[1]] = hd.args
    return cfg, binary, wram, hd.mram


def test_prewarm_counts_no_loop_iterations():
    cfg, binary, _, _ = _va()
    compile_cache.clear()
    compile_cache.prewarm(cfg, binary, n_threads=8)
    s = compile_cache.stats()
    assert s["loop_iters"] == 0 and s["h2d_bytes"] > 0


def test_prewarm_counts_no_lane_cycles():
    cfg, binary, _, _ = _va(n_dpus=3)
    compile_cache.clear()
    compile_cache.prewarm(cfg, binary, n_threads=8)
    s = compile_cache.stats()
    assert (s["lane_cycles"], s["dpu_cycles"]) == (0, 0)


@pytest.mark.parametrize("pad,lanes", [(True, 4), (False, 3)])
def test_lane_cycles_count_every_stepping_lane(pad, lanes):
    cfg, binary, wram, mram = _va(n_dpus=3)     # bucketed to 4 lanes
    compile_cache.clear()
    out = compile_cache.run(cfg, binary, wram, mram, n_threads=8, pad=pad)
    s = compile_cache.stats()
    assert s["lane_cycles"] == lanes * int(out["cycle"].max())
    assert s["dpu_cycles"] == int(out["cycle"].sum())
    if not pad:     # VA's DPUs finish together: every lane is live
        assert s["lane_cycles"] == s["dpu_cycles"]
    compile_cache.run(cfg, binary, wram, mram, n_threads=8, pad=pad)
    assert compile_cache.stats()["lane_cycles"] == 2 * s["lane_cycles"]


@pytest.mark.parametrize("pad,lanes", [(True, 4), (False, 3)])
def test_launch_span_carries_the_lane_count(tmp_path, pad, lanes):
    cfg, binary, wram, mram = _va(n_dpus=3)
    recorded = _profile(tmp_path, lambda: compile_cache.run(
        cfg, binary, wram, mram, n_threads=8, pad=pad))
    (launch,) = [s for s in recorded if s[0] == spans.LAUNCH]
    assert (launch[3]["dpus"], launch[3]["lanes"]) == (3, lanes)


@pytest.mark.parametrize("event_skip", [False, True])
def test_loop_iterations_count_cycles_without_event_skip(event_skip):
    cfg, binary, wram, mram = _va(event_skip=event_skip)
    compile_cache.clear()
    out = compile_cache.run(cfg, binary, wram, mram, n_threads=8)
    iters = compile_cache.stats()["loop_iters"]
    if event_skip:
        # a skipping iteration advances several cycles at once
        assert 0 < iters < out["cycle"].max()
    else:
        assert iters == out["cycle"].max()
    assert "loop_iters" not in out      # never part of the state


def test_h2d_bytes_count_the_uploaded_leaves():
    cfg, binary, wram, mram = _va(n_dpus=4)     # a full DPU bucket
    compile_cache.clear()
    out = compile_cache.run(cfg, binary, wram, mram, n_threads=8)
    P = compile_cache.program_bucket(binary.n_instrs, binary.opcode.shape[0])
    # the state keeps its leaves' shapes through the loop
    want = sum(x.nbytes for x in jax.tree_util.tree_leaves(out)) + \
        sum(a[:P].nbytes for a in binary.arrays)
    assert compile_cache.stats()["h2d_bytes"] == want
    compile_cache.run(cfg, binary, wram, mram, n_threads=8)
    assert compile_cache.stats()["h2d_bytes"] == 2 * want


@pytest.mark.parametrize("backend", ["scalar", "simt"])
def test_engine_executable_is_named_after_its_backend(backend):
    kw = {"simt_width": 4} if backend == "simt" else {}
    cfg, binary, wram, mram = _va(n_dpus=2, **kw)
    be = backends.get(backend)
    st = be.to_carry(be.make_state(cfg, binary, wram, mram, 8))
    P = compile_cache.program_bucket(binary.n_instrs, binary.opcode.shape[0])
    ir = tuple(a[:P] for a in binary.arrays)
    go = compile_cache._make_go(cfg, be, 8)
    assert f"jit_pim_engine_{backend}" in go.lower(ir, st).as_text()
