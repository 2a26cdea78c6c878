"""The rest of PrIM under the default engine with WRAM and MRAM flat in
the loop carry, as past ``engine.FLAT_CARRY_WORDS`` (cut to 0 here: 64
lanes hold less), against the default pins (see ``prim_digests.py``)."""
import pytest

from prim_digests import PRIM_KERNELS, check
from repro.core import engine


@pytest.mark.parametrize("kernel", PRIM_KERNELS)
def test_prim_digests_flat_carry(kernel, monkeypatch):
    monkeypatch.setattr(engine, "FLAT_CARRY_WORDS", 0)
    check(f"{kernel}/default")
