"""The rest of PrIM under the ``forwarding`` engine, pinned bit for bit
(see ``prim_digests.py``)."""
import pytest

from prim_digests import PRIM_KERNELS, check


@pytest.mark.parametrize("kernel", PRIM_KERNELS)
def test_prim_digests_forwarding(kernel):
    check(f"{kernel}/forwarding")
