"""Persistent compiled-engine runtime: every simulation enters XLA here.

Before this module, each ``engine.run`` / ``simt.run`` call rebuilt the
step closure and a fresh ``@jax.jit`` wrapper, so *every* launch paid the
full 14-stage-pipeline retrace (~seconds) — iterated workloads (per-level
BFS, NW sweeps, SSORT's three kernel phases) and every distinct
``launch(dpus=...)`` subset size recompiled from scratch.

The cache kills that three ways:

* **Memoized drivers** — the jitted ``while_loop`` driver is memoized on
  ``(DPUConfig.static_key(), program bucket, DPU bucket, tasklet count,
  MRAM words, backend)``.  A warm relaunch is a dictionary hit plus one
  XLA dispatch.
* **Traced binaries** — the instruction image (the six SoA int32 vectors
  of :class:`isa.Binary`) is passed as *traced operands* instead of
  baked-in closure constants, so two different kernels of the same
  padded shape share one executable.
* **Shape buckets** — the program axis and the DPU axis are padded to
  buckets with masked inactive lanes (``DONE`` status, ``STOP``-filled
  program tail), so ``host.launch(dpus=...)`` subsets of any size — and
  sweeps over system sizes — land on a handful of executables instead of
  one per exact shape.  The program axis takes powers of two.  The DPU
  axis takes powers of two up to :data:`DPU_LANE_TILE` DPUs and, above,
  multiples of that lane tile on a quarter-octave ladder (at most four
  buckets per octave; see :func:`dpu_bucket`), so a large system such as
  PrIM's 2,560-DPU server runs on as many lanes as it has DPUs.  Padded
  DPU lanes never issue, never touch DRAM, and are sliced off before
  results are returned, so bucketed runs are bit-exact vs. unpadded ones.

State buffers are donated to XLA (they are rebuilt per launch), avoiding
a full state copy per step-loop entry.

The jitted driver is named after its backend, ``pim_engine_<backend>``,
so its executable reads ``jit_pim_engine_scalar`` (etc.) in a profiler
trace.  Each launch is a ``repro.launch`` host span with four children
(``prepare``, ``upload``, ``device``, ``readback``; see
:mod:`repro.obs.spans`).

Knobs: :data:`PROGRAM_BUCKET_FLOOR` / :data:`DPU_BUCKET_FLOOR` set the
smallest bucket (smaller floors = tighter shapes but more executables).
:func:`prewarm` compiles ahead of time; :func:`stats` exposes the
hit/miss counters the tests assert on, the engine's loop iterations,
the bytes uploaded per launch and the lane-cycles that padded and
early-finishing lanes step through.  Entry points (never the library or
the tests) call :func:`use_persistent_cache` so compiled executables
also survive the process.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backends
from repro.core import engine
from repro.core.backend import resolve_backend
from repro.core.config import DPUConfig
from repro.obs import spans

#: smallest padded program length (instruction slots)
PROGRAM_BUCKET_FLOOR = 64
#: smallest padded DPU-axis width
DPU_BUCKET_FLOOR = 1
#: the TPU's lane tile: DPU buckets above it are multiples of it
DPU_LANE_TILE = 128


#: persistent XLA cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset —
#: a fixed path in the checkout, so each run finds the last one's entries
DEFAULT_PERSISTENT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    JAX already reads ``JAX_COMPILATION_CACHE_DIR`` when it is set, and
    then no other directory is configured here.  Otherwise the cache goes
    to :data:`DEFAULT_PERSISTENT_CACHE`.  Called by the entry-point
    scripts only, so importing the library changes no JAX setting."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir",
                      str(DEFAULT_PERSISTENT_CACHE))
    return str(DEFAULT_PERSISTENT_CACHE)


def pow2_bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor)."""
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()


def program_bucket(n_instrs: int, capacity: int) -> int:
    """Padded program length for an ``n_instrs``-long kernel.

    One slot past the program is always included (when capacity allows)
    so a fall-through off the last instruction still lands on the
    assembler's ``STOP`` padding, exactly as with full-capacity images."""
    return min(int(capacity), pow2_bucket(n_instrs + 1, PROGRAM_BUCKET_FLOOR))


def dpu_bucket(n_dpus: int) -> int:
    """Padded DPU-axis width for ``n_dpus`` DPUs.

    Up to :data:`DPU_LANE_TILE` DPUs: the smallest power of two (at
    least :data:`DPU_BUCKET_FLOOR`).  Above: ``n_dpus`` rounded up to a
    multiple of the lane tile or of a quarter of ``p``, the largest power
    of two below ``n_dpus``, whichever is larger: 256, 384, 512, 640,
    768, 896, 1024, 1280, ..., 2560, 3072, 3584, 4096.  At most four
    buckets per octave; from 513 DPUs on, under a fifth of the lanes are
    masked."""
    n = int(n_dpus)
    if n <= DPU_LANE_TILE:
        return pow2_bucket(n, DPU_BUCKET_FLOOR)
    p = 1 << ((n - 1).bit_length() - 1)     # p < n <= 2p
    step = max(DPU_LANE_TILE, p // 4)
    return -(-n // step) * step


def _lanes(cfg: DPUConfig, pad: bool) -> int:
    """Width of the engine's DPU axis for a launch of ``cfg``."""
    return dpu_bucket(cfg.n_dpus) if pad else cfg.n_dpus


@dataclass
class _Entry:
    """One cached executable: a jitted binary-agnostic while-loop driver."""

    go: Callable
    key: tuple
    launches: int = 0

    def xla_cache_size(self) -> Optional[int]:
        """Number of traces the underlying jit has seen (1 == the shape
        bucket is doing its job); None if the runtime doesn't expose it."""
        try:
            return self.go._cache_size()
        except AttributeError:
            return None


_LOCK = threading.Lock()
_ENTRIES: Dict[tuple, _Entry] = {}
_HITS = 0
_MISSES = 0
_LOOP_ITERS = 0
_H2D_BYTES = 0
_LANE_CYCLES = 0
_DPU_CYCLES = 0


def _make_go(cfg: DPUConfig, be: "backends.ExecBackend", T: int) -> Callable:
    """The jitted driver: ``(ir, state) -> (final state, loop iterations)``.
    The iteration count rides beside the state, never inside it."""
    step, cond = be.step_driver(cfg, T)

    def drive(ir, st):
        return jax.lax.while_loop(lambda c: cond(c[0]),
                                  lambda c: (step(ir, c[0]), c[1] + 1),
                                  (st, jnp.int32(0)))

    drive.__name__ = drive.__qualname__ = f"pim_engine_{be.name}"
    # the state is rebuilt per launch -> donate it; the instruction image
    # is reused across launches -> never donated
    return jax.jit(drive, donate_argnums=(1,))


def _get_entry(cfg: DPUConfig, be: "backends.ExecBackend", P: int, Dp: int,
               T: int, M: int) -> Tuple[_Entry, bool]:
    """The cached executable for this shape, and whether it was a hit."""
    global _HITS, _MISSES
    key = (be.name, be.static_key(cfg), P, Dp, T, M)
    with _LOCK:
        entry = _ENTRIES.get(key)
        if entry is None:
            _MISSES += 1
            entry = _Entry(go=_make_go(cfg, be, T), key=key)
            _ENTRIES[key] = entry
            return entry, False
        _HITS += 1
        return entry, True


def _padded_state(cfg: DPUConfig, be: "backends.ExecBackend", binary,
                  wram_init, mram_init, T: int, Dp: int,
                  all_done: bool = False, ndpus_reg: int = None):
    """Initial state padded to the DPU bucket, masked lanes DONE.

    ``ndpus_reg`` overrides the ``N_DPUS`` register the kernels read —
    runtime state, not part of any cache key.  The fault runtime uses it
    so a degraded subset launch (survivors of a logically ``n``-wide
    system) still sees the logical width."""
    D = cfg.n_dpus
    if Dp != D:
        wram_init = np.concatenate(
            [wram_init, np.zeros((Dp - D, wram_init.shape[1]), np.int32)])
        mram_init = np.concatenate(
            [mram_init, np.zeros((Dp - D, mram_init.shape[1]), np.int32)])
        cfg = cfg.replace(n_dpus=Dp)
    st = be.make_state(cfg, binary, wram_init, mram_init, T)
    if Dp != D:
        be.pad_lanes(cfg, st, D)                # masked lanes never issue
    if ndpus_reg is not None:
        be.set_ndpus(st, D, ndpus_reg)
    if all_done:
        be.finish_all(st)
    return st


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def _launch(cfg: DPUConfig, binary, wram_init, mram_init, T: int,
            be: "backends.ExecBackend", pad: bool, all_done: bool = False,
            ndpus_reg: int = None):
    """Build, upload and run one launch.  Returns ``(entry, cache hit,
    final device state in the backend's carry form, loop iterations as a
    device scalar)``, the device work finished."""
    global _H2D_BYTES
    with spans.span(spans.LAUNCH_PREPARE):
        be.validate(cfg, binary, T)
        wram_init = np.ascontiguousarray(np.asarray(wram_init, np.int32))
        mram_init = np.ascontiguousarray(np.asarray(mram_init, np.int32))
        capacity = binary.opcode.shape[0]
        P = program_bucket(binary.n_instrs, capacity) if pad else capacity
        Dp = _lanes(cfg, pad)
        st0 = be.to_carry(_padded_state(cfg, be, binary, wram_init,
                                        mram_init, T, Dp, all_done=all_done,
                                        ndpus_reg=ndpus_reg))
        entry, hit = _get_entry(cfg, be, P, Dp, T, mram_init.shape[1])
        ir = tuple(a[:P] for a in binary.arrays)
    nbytes = _nbytes(st0) + _nbytes(ir)
    with spans.span(spans.LAUNCH_UPLOAD, nbytes=nbytes):
        st0 = jax.tree_util.tree_map(jnp.asarray, st0)
        ir = tuple(jnp.asarray(a) for a in ir)
    with _LOCK:
        _H2D_BYTES += nbytes
    with spans.span(spans.LAUNCH_DEVICE):
        out, iters = entry.go(ir, st0)
        jax.block_until_ready(out)
    entry.launches += 1
    return entry, hit, out, iters


def _count_iters(iters) -> None:
    global _LOOP_ITERS
    n = int(iters)
    with _LOCK:
        _LOOP_ITERS += n


def _count_cycles(cycles: np.ndarray, lanes: int) -> None:
    """Count one launch from its real DPUs' final cycles (host numpy,
    already read back): every lane steps until the slowest DPU is done,
    and each real DPU is simulated for its own cycles."""
    global _LANE_CYCLES, _DPU_CYCLES
    lane, live = lanes * int(cycles.max()), int(cycles.sum())
    with _LOCK:
        _LANE_CYCLES += lane
        _DPU_CYCLES += live


def _launch_span(cfg: DPUConfig, be: "backends.ExecBackend", pad: bool):
    return spans.span(spans.LAUNCH, sim_id=spans.sim_id(), backend=be.name,
                      dpus=cfg.n_dpus, lanes=_lanes(cfg, pad))


def run(cfg: DPUConfig, binary, wram_init, mram_init, n_threads: int = None,
        backend: str = None, pad: bool = True,
        ndpus_reg: int = None) -> Dict[str, np.ndarray]:
    """Simulate ``binary`` to completion through the compiled-engine cache.

    The launch path behind ``engine.run`` and ``simt.run``:

    * ``backend`` — a registered :class:`repro.core.backend.ExecBackend`
      name (default: :func:`~repro.core.backend.resolve_backend` —
      ``cfg.backend``, else by ``cfg.simt_width``);
    * ``pad=False`` disables shape bucketing (exact shapes; used by the
      bit-exactness tests as the unpadded reference);
    * ``ndpus_reg`` overrides the ``N_DPUS`` register (degraded remap
      launches keep the pre-fault logical width) — it changes initial
      state only, never the cache key, so degraded launches stay
      warm-cache.

    Returns the final state as a host-numpy pytree sliced back to the
    logical ``cfg.n_dpus`` rows."""
    be = backends.get(resolve_backend(cfg, backend))
    T = n_threads or cfg.n_tasklets
    with _launch_span(cfg, be, pad) as launch_span:
        _, hit, out, iters = _launch(cfg, binary, wram_init, mram_init, T,
                                     be, pad, ndpus_reg=ndpus_reg)
        launch_span.set_metadata(cache="hit" if hit else "miss")
        with spans.span(spans.LAUNCH_READBACK, nbytes=_nbytes(out)):
            out = be.from_carry(jax.tree_util.tree_map(np.asarray, out))
            if out["status"].shape[0] != cfg.n_dpus:
                out = jax.tree_util.tree_map(lambda x: x[:cfg.n_dpus], out)
            _count_iters(iters)
        _count_cycles(out["cycle"], _lanes(cfg, pad))
    return out


def prewarm(cfg: DPUConfig, binary, mram_words: int = None,
            n_threads: int = None, backend: str = None) -> tuple:
    """Compile (or look up) the executable a later :func:`run` will use,
    without simulating anything: launches an all-``DONE`` state, so the
    while-loop exits at the first predicate check but XLA still traces
    and compiles the full cycle step.  Returns the cache key.

    ``mram_words`` must match the MRAM image width of the real launch
    (default: ``cfg.mram_words``)."""
    be = backends.get(resolve_backend(cfg, backend))
    T = n_threads or cfg.n_tasklets
    M = mram_words or cfg.mram_words
    wram = np.zeros((cfg.n_dpus, 1), np.int32)
    mram = np.zeros((cfg.n_dpus, M), np.int32)
    with _launch_span(cfg, be, True) as launch_span:
        entry, hit, _, iters = _launch(cfg, binary, wram, mram, T, be, True,
                                       all_done=True)
        launch_span.set_metadata(cache="hit" if hit else "miss")
        _count_iters(iters)
    return entry.key


# ---------------------------------------------------------------------------
# introspection (tests + benchmarks)
# ---------------------------------------------------------------------------


def stats() -> Dict[str, int]:
    """Cumulative counters.  ``misses`` counts executable *builds* — a
    same-shape relaunch must leave it unchanged.  ``loop_iters`` counts
    the engine's ``while_loop`` iterations (one may advance several
    simulated cycles under ``event_skip``; an all-``DONE`` prewarm adds
    none), ``h2d_bytes`` the bytes uploaded to the device (state leaves
    plus instruction image).  ``lane_cycles`` sums, over :func:`run`'s
    launches, the engine's lane count (the padded DPU bucket) times the
    launch's slowest-DPU cycles; ``dpu_cycles`` sums each real DPU's
    own cycles.  ``dpu_cycles / lane_cycles`` is the share of lane-cycles
    that simulate a live DPU: padded lanes and lanes whose DPU finished
    early lower it.  Both are counted on the host from the state read
    back; an all-``DONE`` prewarm adds none."""
    with _LOCK:
        return {
            "entries": len(_ENTRIES),
            "hits": _HITS,
            "misses": _MISSES,
            "launches": sum(e.launches for e in _ENTRIES.values()),
            "loop_iters": _LOOP_ITERS,
            "h2d_bytes": _H2D_BYTES,
            "lane_cycles": _LANE_CYCLES,
            "dpu_cycles": _DPU_CYCLES,
        }


def cache_info():
    """Per-executable detail: key, launch count, XLA trace count."""
    with _LOCK:
        return [{"key": e.key, "launches": e.launches,
                 "xla_cache_size": e.xla_cache_size()}
                for e in _ENTRIES.values()]


def clear():
    """Drop every cached executable and zero the counters (tests)."""
    global _HITS, _MISSES, _LOOP_ITERS, _H2D_BYTES, _LANE_CYCLES, _DPU_CYCLES
    with _LOCK:
        _ENTRIES.clear()
        _HITS = _MISSES = _LOOP_ITERS = _H2D_BYTES = 0
        _LANE_CYCLES = _DPU_CYCLES = 0
