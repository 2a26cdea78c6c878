"""Pluggable execution backends: the seam between the host runtime and a
simulated PIM microarchitecture.

Before this module the engine-vs-SIMT choice was an if/else on the
strings ``"scalar" | "simt"`` scattered across ``compile_cache.py``
(``_make_go``, ``_get_entry`` keys, ``_padded_state``, duplicated
``backend=None`` resolution) and ``host.py`` (``_launch_engine``).  An
:class:`ExecBackend` packages everything the compiled-engine cache and
the host launch path need to run *any* architecture:

* :meth:`~ExecBackend.make_state` — initial state as a host-numpy pytree
  (leading DPU axis; must contain ``"status"``, ``"cycle"`` and
  ``"mram"`` so the generic padding/readback/fault machinery works);
* :meth:`~ExecBackend.step_driver` — the traced per-cycle step and the
  while-loop termination predicate;
* :meth:`~ExecBackend.static_key` — the config part of the compile-cache
  key (two configs with equal keys share one XLA executable);
* :meth:`~ExecBackend.pad_lanes` — mask DPU-bucket padding rows so they
  never issue;
* :meth:`~ExecBackend.report` — final state -> :class:`KernelReport`;
* :meth:`~ExecBackend.to_carry` / :meth:`~ExecBackend.from_carry` — the
  state's layout on the device, inside one launch (the identity unless a
  backend's step wants another).

Backends register by name; :func:`resolve_backend` is the one place the
default (``cfg.backend``, else SIMT-iff-``simt_width``) is decided.
Registering a new architecture is three steps::

    class MyBackend(ExecBackend):
        name = "mine"
        ...                       # implement the protocol
    register(MyBackend())
    cfg = DPUConfig(backend="mine")   # every launch now runs on it

The UPMEM-style scalar and SIMT engines are the first two registered
implementations (bit-exact with the pre-seam dispatch); the HBM-PIM
all-bank targets (``"hbmpim"`` / ``"hbmpim_cmd"``) load lazily from
:mod:`repro.core.hbmpim` on first lookup.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from repro.core import engine, isa, simt, stats
from repro.core.config import DPUConfig


class ExecBackend:
    """One simulated execution architecture (see module docstring).

    The base class implements the engine-family state layout (per-tasklet
    ``status``/``regs`` arrays); backends with a different layout override
    :meth:`pad_lanes` / :meth:`set_ndpus` / :meth:`finish_all` too."""

    #: registry name; also the first element of every compile-cache key
    name: str = "?"

    # ---- protocol ----------------------------------------------------------
    def validate(self, cfg: DPUConfig, binary, n_threads: int) -> None:
        """Raise if (cfg, binary, n_threads) cannot run on this backend."""

    def make_state(self, cfg: DPUConfig, binary, wram_init, mram_init,
                   n_threads: int):
        """Initial microarchitectural state (host-numpy pytree)."""
        raise NotImplementedError

    def step_driver(self, cfg: DPUConfig, n_threads: int) -> Tuple:
        """``(step, cond)``: the traced ``(ir, state) -> state`` cycle
        function and the while-loop predicate."""
        raise NotImplementedError

    def static_key(self, cfg: DPUConfig) -> tuple:
        """Hashable config identity for the compile cache (everything the
        traced step closes over)."""
        return cfg.static_key()

    def report(self, name: str, cfg: DPUConfig, st, n_threads: int
               ) -> "stats.KernelReport":
        """Aggregate the final state's counters into a KernelReport."""
        return stats.report_from_state(name, cfg, st, n_threads)

    def to_carry(self, st):
        """The state as the step takes it, from :meth:`make_state`'s
        layout (applied on the host before the upload)."""
        return st

    def from_carry(self, st):
        """Inverse of :meth:`to_carry` (applied on the host after the
        readback)."""
        return st

    # ---- lane masking (engine-family layout; override if different) --------
    def pad_lanes(self, cfg: DPUConfig, st, logical_d: int) -> None:
        """Mask DPU-bucket padding rows (``logical_d:``) so they never
        issue, and keep kernels seeing the logical system size."""
        st["status"][logical_d:] = engine.DONE
        st["regs"][:, :, isa.R_NDPU] = logical_d

    def set_ndpus(self, st, logical_d: int, ndpus_reg: int) -> None:
        """Override the ``N_DPUS`` register of the live rows (degraded
        remap launches keep the pre-fault logical width)."""
        st["regs"][:logical_d, :, isa.R_NDPU] = int(ndpus_reg)

    def finish_all(self, st) -> None:
        """Mark every lane DONE (prewarm compiles without simulating)."""
        st["status"][:] = engine.DONE


class ScalarBackend(ExecBackend):
    """Baseline UPMEM-style MIMD DPU (in-order 14-stage scalar pipeline)."""

    name = "scalar"

    def make_state(self, cfg, binary, wram_init, mram_init, n_threads):
        return engine.make_state_np(cfg, binary, wram_init, mram_init,
                                    n_threads)

    def step_driver(self, cfg, n_threads):
        return engine.make_step_traced(cfg), engine.make_cond(cfg)

    def to_carry(self, st):
        return engine.to_carry(st)

    def from_carry(self, st):
        return engine.from_carry(st)


class SimtBackend(ExecBackend):
    """SIMT vector DPU (case study #1): warps of ``simt_width`` tasklets."""

    name = "simt"

    def validate(self, cfg, binary, n_threads):
        if cfg.simt_width <= 0:
            raise AssertionError("simt backend needs simt_width > 0")
        if n_threads % cfg.simt_width != 0:
            raise AssertionError(
                "n_tasklets must be a multiple of warp width")

    def make_state(self, cfg, binary, wram_init, mram_init, n_threads):
        return simt.make_state_np(cfg, binary, wram_init, mram_init,
                                  n_threads)

    def step_driver(self, cfg, n_threads):
        return simt.make_step_traced(cfg), engine.make_cond(cfg)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ExecBackend] = {}

#: backends imported on first get() — registering at import time would
#: make repro.core.backend depend on every architecture module
_LAZY = {
    "hbmpim": "repro.core.hbmpim",
    "hbmpim_cmd": "repro.core.hbmpim",
}


def register(backend: ExecBackend) -> ExecBackend:
    """Add (or replace) a backend under ``backend.name``."""
    if not backend.name or backend.name == "?":
        raise ValueError("backend must carry a non-empty name")
    _REGISTRY[backend.name] = backend
    return backend


def get(name: str) -> ExecBackend:
    """Look up a registered backend (loading lazy modules on demand)."""
    be = _REGISTRY.get(name)
    if be is None and name in _LAZY:
        import importlib
        importlib.import_module(_LAZY[name])
        be = _REGISTRY.get(name)
    if be is None:
        raise KeyError(
            f"unknown execution backend {name!r} (registered: "
            f"{', '.join(sorted(set(_REGISTRY) | set(_LAZY)))})")
    return be


def names() -> tuple:
    """Every addressable backend name (registered + lazy)."""
    return tuple(sorted(set(_REGISTRY) | set(_LAZY)))


def resolve_backend(cfg: DPUConfig, backend: Optional[str] = None) -> str:
    """The backend name a launch of ``cfg`` runs on.

    Precedence: an explicit ``backend`` argument, then ``cfg.backend``,
    then the legacy default — ``"simt"`` iff ``cfg.simt_width > 0``,
    else ``"scalar"``.  This is the single home of the default-resolution
    logic that used to be duplicated in ``compile_cache.run`` and
    ``compile_cache.prewarm``."""
    if backend:
        return backend
    if cfg.backend:
        return cfg.backend
    return "simt" if cfg.simt_width > 0 else "scalar"


register(ScalarBackend())
register(SimtBackend())
