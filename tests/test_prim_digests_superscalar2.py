"""The rest of PrIM under the ``superscalar2`` engine, pinned bit for bit
(see ``prim_digests.py``)."""
import pytest

from prim_digests import PRIM_KERNELS, check


@pytest.mark.parametrize("kernel", PRIM_KERNELS)
def test_prim_digests_superscalar2(kernel):
    check(f"{kernel}/superscalar2")
