"""Compiled-engine cache: executable reuse, shape bucketing, bit-exactness."""
import jax
import numpy as np
import pytest

import repro.workloads as wl
from repro.core import backend as backends
from repro.core import compile_cache, engine
from repro.core.asm import Program
from repro.core.config import DPUConfig
from repro.core.host import PIMSystem


def _setup(name="VA", n_dpus=4, scale=0.02, n_threads=8, **kw):
    cfg = DPUConfig(n_dpus=n_dpus, n_tasklets=16, mram_bytes=1 << 16, **kw)
    W = wl.get(name)
    hd = W.host_data(cfg, scale, 0)
    binary = W.build(n_threads).binary(cfg.iram_instrs)
    wram = np.zeros((n_dpus, 16), np.int32)
    wram[:, :hd.args.shape[1]] = hd.args
    return cfg, binary, wram, hd.mram, hd


def _chain_binary(op_name, n, cfg):
    p = Program(op_name, 1)
    r = p.reg("r")
    for _ in range(n):
        getattr(p, op_name)(r, r, 3)
    p.stop()
    return p.binary(cfg.iram_instrs)


# ---------------------------------------------------------------------------
# cache hit/miss accounting
# ---------------------------------------------------------------------------


def test_warm_relaunch_zero_new_compiles():
    """Same-shape relaunches must never build a new executable."""
    cfg, binary, wram, mram, _ = _setup()
    compile_cache.clear()
    out0 = engine.run(cfg, binary, wram, mram, 8)
    assert compile_cache.stats()["misses"] == 1
    for _ in range(3):
        out = engine.run(cfg, binary, wram, mram, 8)
    s = compile_cache.stats()
    assert s["misses"] == 1, s          # zero new compilations
    assert s["hits"] == 3, s
    # the jitted driver itself retraced nothing either
    (info,) = compile_cache.cache_info()
    assert info["xla_cache_size"] in (None, 1), info
    for k in out0:
        assert np.array_equal(out0[k], out[k]), k


def test_different_kernels_share_executable():
    """Two kernels of the same padded shape reuse one executable (the
    binary is a traced operand, not a baked constant)."""
    cfg = DPUConfig(n_dpus=2, n_tasklets=1, mram_bytes=1 << 14)
    b_add = _chain_binary("add", 20, cfg)
    b_xor = _chain_binary("xor", 25, cfg)
    assert (compile_cache.program_bucket(b_add.n_instrs, cfg.iram_instrs)
            == compile_cache.program_bucket(b_xor.n_instrs, cfg.iram_instrs))
    compile_cache.clear()
    wram = np.zeros((2, 16), np.int32)
    mram = np.zeros((2, cfg.mram_words), np.int32)
    engine.run(cfg, b_add, wram, mram, 1)
    engine.run(cfg, b_xor, wram, mram, 1)
    s = compile_cache.stats()
    assert s["entries"] == 1 and s["misses"] == 1, s


def test_subset_launches_share_bucket_executable():
    """host.launch(dpus=...) subsets within one pow2 bucket reuse the
    full-system executable instead of compiling per subset size."""
    cfg, binary, _, _, hd = _setup(n_dpus=8)
    sys_ = PIMSystem(cfg)
    compile_cache.clear()
    st_full, _ = sys_.launch("VA", binary, hd.args, hd.mram, n_threads=8)
    assert compile_cache.stats()["misses"] == 1
    for k in (5, 6, 7, 8):
        st, _ = sys_.launch("VA", binary, hd.args, hd.mram, n_threads=8,
                            dpus=list(range(k)))
        assert st["status"].shape[0] == k
        # subset rows are the same simulation as the full system's rows
        assert np.array_equal(st["mram"], st_full["mram"][:k])
    s = compile_cache.stats()
    assert s["misses"] == 1, s          # every subset size was a hit


def test_prewarm_compiles_ahead():
    cfg, binary, wram, mram, _ = _setup(n_dpus=2)
    compile_cache.clear()
    key = compile_cache.prewarm(cfg, binary, mram_words=mram.shape[1],
                                n_threads=8)
    assert compile_cache.stats()["misses"] == 1
    engine.run(cfg, binary, wram, mram, 8)
    s = compile_cache.stats()
    assert s["misses"] == 1 and s["hits"] == 1, s
    assert key in [i["key"] for i in compile_cache.cache_info()]


# ---------------------------------------------------------------------------
# padding / masking bit-exactness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["VA", "BS", "RED"])
def test_padded_bit_exact_engine(name):
    """A bucketed launch (D=5 padded to 8, program padded to its bucket)
    must match the exact-shape run on every state array."""
    cfg, binary, wram, mram, _ = _setup(name, n_dpus=5)
    padded = compile_cache.run(cfg, binary, wram, mram, 8, pad=True)
    exact = compile_cache.run(cfg, binary, wram, mram, 8, pad=False)
    assert padded["status"].shape == exact["status"].shape
    for k in exact:
        assert np.array_equal(padded[k], exact[k]), k


def test_padded_bit_exact_simt():
    cfg, binary, wram, mram, _ = _setup(
        "VA", n_dpus=3, simt_width=4, coalescing=True)
    padded = compile_cache.run(cfg, binary, wram, mram, 8, pad=True)
    exact = compile_cache.run(cfg, binary, wram, mram, 8, pad=False)
    for k in exact:
        assert np.array_equal(padded[k], exact[k]), k


def test_flat_carry_launch_returns_lane_memories(monkeypatch):
    """With WRAM and MRAM flat on the device (``FLAT_CARRY_WORDS`` cut to
    0 here: the real threshold needs more than 512 lanes), a padded
    launch (5 DPUs on 8 lanes) returns ``[5, W]`` / ``[5, M]``, word for
    word the unpadded ``[D, W]`` run's, in every state leaf."""
    cfg, binary, wram, mram, _ = _setup("VA", n_dpus=5)
    lanes = compile_cache.run(cfg, binary, wram, mram, 8, pad=False)
    monkeypatch.setattr(engine, "FLAT_CARRY_WORDS", 0)
    flat = compile_cache.run(cfg, binary, wram, mram, 8, pad=True)
    compile_cache.clear()
    for k, words in (("wram", cfg.wram_words), ("mram", mram.shape[1])):
        assert flat[k].shape == (5, words), k
    for k in lanes:
        assert np.array_equal(flat[k], lanes[k]), k


@pytest.mark.parametrize("backend,flat", [("scalar", True), ("simt", False)])
def test_driver_takes_the_backends_carry_form(monkeypatch, backend, flat):
    """``ExecBackend.to_carry`` shapes what the jitted driver is given:
    flat WRAM and MRAM for the scalar engine past ``FLAT_CARRY_WORDS``
    (cut to 0 here), the identity for simt."""
    monkeypatch.setattr(engine, "FLAT_CARRY_WORDS", 0)
    kw = {"simt_width": 4} if backend == "simt" else {}
    cfg, binary, wram, mram, _ = _setup("VA", n_dpus=3, **kw)
    seen = {}
    make_go = compile_cache._make_go

    def spy(cfg, be, T):
        go = make_go(cfg, be, T)

        def call(ir, st):
            seen.update(wram=st["wram"].shape, mram=st["mram"].shape)
            return go(ir, st)
        return call

    monkeypatch.setattr(compile_cache, "_make_go", spy)
    compile_cache.clear()
    out = compile_cache.run(cfg, binary, wram, mram, 8, backend=backend)
    compile_cache.clear()
    Dp = compile_cache.dpu_bucket(3)
    lanes = {"wram": (Dp, cfg.wram_words), "mram": (Dp, mram.shape[1])}
    for k, shape in lanes.items():
        assert seen[k] == ((Dp * shape[1],) if flat else shape), k
        assert out[k].shape == (3, shape[1]), k


def test_scalar_carry_is_flat_past_the_threshold():
    """One rank keeps ``[D, W]`` lane memories; the 2,560-lane server's
    and other wide systems' are flat (shapes only: nothing is
    allocated)."""
    be = backends.get("scalar")
    for lanes, flat in ((64, False), (512, False), (1024, True),
                        (2560, True), (4096, True)):
        st = {"status": jax.ShapeDtypeStruct((lanes, 16), np.int32),
              "wram": jax.ShapeDtypeStruct((lanes, 1 << 14), np.int32),
              "mram": jax.ShapeDtypeStruct((lanes, 1 << 16), np.int32)}
        carry = jax.eval_shape(be.to_carry, st)
        assert carry["wram"].ndim == carry["mram"].ndim == 2 - flat, lanes
        back = jax.eval_shape(be.from_carry, carry)
        assert {k: x.shape for k, x in back.items()} == \
            {k: x.shape for k, x in st.items()}


def test_flat_carry_refuses_an_index_past_int32():
    """2**15 DPUs of 256 KiB MRAM: 2**31 words, one past the flat index
    (zero-stride views, so nothing is allocated)."""
    D = 1 << 15
    st = {"status": np.zeros((D, 16), np.int32),
          "wram": np.broadcast_to(np.int32(0), (D, 1 << 14)),
          "mram": np.broadcast_to(np.int32(0), (D, 1 << 16))}
    with pytest.raises(ValueError, match="2\\*\\*31"):
        engine.to_carry(st)


def test_padded_lanes_see_logical_system_size():
    """Kernels read N_DPUS from a boot register — padding must not leak
    the bucket size into it."""
    cfg = DPUConfig(n_dpus=3, n_tasklets=1, mram_bytes=1 << 14)
    p = Program("ndpu", 1)
    r = p.reg("r")
    from repro.core.asm import N_DPUS, ZERO
    p.add(r, N_DPUS, 0)
    p.sw(ZERO, 64, r)
    p.stop()
    binary = p.binary(cfg.iram_instrs)
    wram = np.zeros((3, 16), np.int32)
    mram = np.zeros((3, cfg.mram_words), np.int32)
    st = engine.run(cfg, binary, wram, mram, 1)
    assert st["status"].shape[0] == 3
    assert list(st["wram"][:, 16]) == [3, 3, 3]


# ---------------------------------------------------------------------------
# key & bucket mechanics
# ---------------------------------------------------------------------------


def test_static_key_ignores_host_knobs():
    cfg = DPUConfig(n_dpus=4)
    same = cfg.replace(n_dpus=2, n_ranks=2, n_channels=2, fabric="direct",
                       h2d_gbps_per_dpu=9.9, channel_contention=1.5,
                       mram_bytes=1 << 16)
    diff = cfg.replace(forwarding=True)
    assert cfg.static_key() == same.static_key()
    assert cfg.static_key() != diff.static_key()
    assert hash(cfg) is not None  # frozen dataclass stays hashable


def test_bucket_shapes():
    assert compile_cache.pow2_bucket(1) == 1
    assert compile_cache.pow2_bucket(5) == 8
    assert compile_cache.dpu_bucket(2048) == 2048
    cap = 4096
    for n in (1, 63, 64, 100, cap - 1, cap):
        b = compile_cache.program_bucket(n, cap)
        assert b <= cap and (b & (b - 1)) == 0
        assert b >= min(n + 1, cap)  # room for a STOP pad slot


@pytest.mark.parametrize("n,bucket", [
    *((n, 1 << (n - 1).bit_length()) for n in (1, 2, 3, 5, 63, 64, 65,
                                                100, 127, 128)),
    (129, 256), (300, 384), (640, 640), (2556, 2560), (2560, 2560),
    (2561, 3072), (4096, 4096)])
def test_dpu_bucket_rule(n, bucket):
    """Powers of two up to 128 DPUs (one rank's 64 keep bucket 64);
    above, multiples of the 128-lane tile on which PrIM's two systems
    (640 and 2,556 DPUs) and the 2,560-DPU server land without a spare
    rung."""
    assert compile_cache.dpu_bucket(n) == bucket


def test_dpu_bucket_is_the_power_of_two_up_to_128():
    assert [compile_cache.dpu_bucket(n) for n in range(1, 129)] == \
        [1 << (n - 1).bit_length() for n in range(1, 129)]


def test_dpu_bucket_holds_n_on_the_lane_tile():
    for n in range(1, 20001):
        b = compile_cache.dpu_bucket(n)
        assert b >= n, n
        assert n <= 128 or b % 128 == 0, n


def test_dpu_bucket_has_four_per_octave_at_most():
    buckets = {compile_cache.dpu_bucket(n) for n in range(1, 1 << 16)}
    for k in range(17):
        octave = [x for x in buckets if 1 << k < x <= 2 << k]
        assert len(octave) <= 4, (k, sorted(octave))


def test_padded_bit_exact_off_pow2_bucket():
    """300 DPUs on 384 lanes, a bucket that is no power of two: the
    padded launch matches the exact-shape run on every state array and
    counts its 84 masked lanes in ``lane_cycles``."""
    cfg = DPUConfig(n_dpus=300, n_tasklets=2, mram_bytes=1 << 12)
    p = Program("ids", 2)
    r = p.reg("r")
    from repro.core.asm import DPU_ID, N_DPUS, ZERO
    p.mul(r, DPU_ID, 3)
    p.add(r, r, N_DPUS)
    p.sw(ZERO, 64, r)
    p.stop()
    binary = p.binary(cfg.iram_instrs)
    wram = np.zeros((300, 16), np.int32)
    mram = np.zeros((300, cfg.mram_words), np.int32)
    assert compile_cache.dpu_bucket(300) == 384
    compile_cache.clear()
    padded = compile_cache.run(cfg, binary, wram, mram, 2, pad=True)
    s = compile_cache.stats()
    compile_cache.clear()
    exact = compile_cache.run(cfg, binary, wram, mram, 2, pad=False)
    compile_cache.clear()
    assert padded["status"].shape == exact["status"].shape == (300, 2)
    for k in exact:
        assert np.array_equal(padded[k], exact[k]), k
    assert list(padded["wram"][[0, 1, 299], 16]) == [300, 303, 1197]
    cycles = int(padded["cycle"].max())
    assert s["lane_cycles"] == 384 * cycles
    assert s["dpu_cycles"] == int(padded["cycle"].sum())


def test_bucket_floor_knob():
    assert compile_cache.program_bucket(
        1, 4096) == compile_cache.PROGRAM_BUCKET_FLOOR
