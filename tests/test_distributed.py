"""Multi-device distribution tests (8 fake devices in a subprocess so the
main test process keeps its single-device jax state)."""
import os
import subprocess
import sys

import pytest

_SCRIPT = os.path.join(os.path.dirname(__file__), "_dist_scenarios.py")


@pytest.mark.slow
def test_distributed_scenarios():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, _SCRIPT], capture_output=True, text=True,
        timeout=1500, env=env)
    sys.stdout.write(out.stdout[-4000:])
    sys.stderr.write(out.stderr[-4000:])
    assert out.returncode == 0, out.stderr[-3000:]
    for marker in ("SHARDED_TRAIN OK", "MOE_EP OK", "PIPELINE OK",
                   "COMPRESSED_DP OK", "ELASTIC OK", "DRYRUN_SMALL OK"):
        assert marker in out.stdout, marker
