"""The scalar engine pinned bit for bit on the rest of PrIM.

``test_engine_digests.py`` pins VA, BFS, SYNC and the cacheable kernels;
this pins the other thirteen PrIM workloads (``PRIM_KERNELS``) under
``VARIANTS`` through the same ``digest_case``, against
``data/prim_digests.json``.  The cases are spread over the
``test_prim_digests_*`` files, one engine variant each, so that no test
file runs long on one worker.  They run on ``N_DPUS`` lanes, not 64:
every lane steps the same code, and on the CPU the DMA-heavy kernels
cost time in proportion to the lane count (SpMV's run, compile
excluded: 3.9 s at 16 lanes, 8.5 s at 32, 12 s at 64), while a lower
scale does not shorten them (0.03 is already at their size floor).
Regenerate only for a change that is meant to alter simulated results:
``PYTHONPATH=src python tests/prim_digests.py --write``.
"""
import json
import sys
from pathlib import Path

from test_engine_digests import PRIM_KERNELS, digest_case

PINS = Path(__file__).parent / "data" / "prim_digests.json"
N_DPUS = 16
VARIANTS = ("default", "forwarding", "superscalar2", "mmu")
CASES = [f"{k}/{v}" for v in VARIANTS for k in PRIM_KERNELS]


def check(case: str):
    assert digest_case(case, N_DPUS) == json.loads(PINS.read_text())[case]


if __name__ == "__main__" and sys.argv[1:] == ["--write"]:
    PINS.write_text(json.dumps({c: digest_case(c, N_DPUS) for c in CASES},
                               indent=1, sort_keys=True) + "\n")
