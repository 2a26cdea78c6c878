"""The rest of PrIM under the ``mmu`` engine, pinned bit for bit
(see ``prim_digests.py``)."""
import pytest

from prim_digests import PRIM_KERNELS, check


@pytest.mark.parametrize("kernel", PRIM_KERNELS)
def test_prim_digests_mmu(kernel):
    check(f"{kernel}/mmu")
