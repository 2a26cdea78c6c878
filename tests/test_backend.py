"""ExecBackend seam: registry mechanics, default resolution, and
bit-exactness pins vs. the pre-refactor dispatch.

The golden numbers were captured on the commit *before* the backend
extraction (string-dispatch ``compile_cache``/``host``): the seam must
not change a single simulated value."""
import pytest

from repro.core import backend as backends
from repro.goldens import GOLDENS, golden_config as _cfg, run_golden


# ---------------------------------------------------------------------------
# registry + resolution
# ---------------------------------------------------------------------------


def test_registry_has_engine_families():
    assert backends.get("scalar").name == "scalar"
    assert backends.get("simt").name == "simt"
    for name in ("scalar", "simt", "hbmpim", "hbmpim_cmd"):
        assert name in backends.names()


def test_unknown_backend_lists_names():
    with pytest.raises(KeyError) as e:
        backends.get("nope")
    assert "scalar" in str(e.value) and "hbmpim" in str(e.value)


def test_resolve_backend_precedence():
    # explicit argument > cfg.backend > simt_width default
    cfg = _cfg()
    assert backends.resolve_backend(cfg) == "scalar"
    assert backends.resolve_backend(cfg.replace(simt_width=4)) == "simt"
    assert backends.resolve_backend(cfg.replace(backend="hbmpim")) == "hbmpim"
    assert backends.resolve_backend(
        cfg.replace(backend="hbmpim", simt_width=4)) == "hbmpim"
    assert backends.resolve_backend(
        cfg.replace(backend="hbmpim"), "scalar") == "scalar"


def test_lazy_hbmpim_registration():
    be = backends.get("hbmpim_cmd")
    assert be.name == "hbmpim_cmd"


def test_cfg_backend_not_in_static_key():
    # the backend name is keyed explicitly by the compile cache; the
    # config's static identity must not fork on it
    cfg = _cfg()
    assert cfg.static_key() == cfg.replace(backend="hbmpim").static_key()


def test_simt_backend_validates_width():
    be = backends.get("simt")
    with pytest.raises(AssertionError):
        be.validate(_cfg(), None, 8)            # simt_width == 0
    with pytest.raises(AssertionError):
        be.validate(_cfg(simt_width=3), None, 8)  # 8 % 3 != 0


# ---------------------------------------------------------------------------
# bit-exactness pins (pre-refactor goldens)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(GOLDENS))
def test_bit_exact_vs_pre_refactor(name):
    cycles, issued, total, kernel = run_golden(name)
    want_cycles, want_issued, want_total, want_kernel = GOLDENS[name]
    assert cycles == want_cycles
    assert issued == want_issued
    assert total == want_total
    assert kernel == want_kernel
