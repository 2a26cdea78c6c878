#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip and print its result line.

    python3 pimbench/run.py --workload rank64.va --seed 7 --seconds 30 --trace 0

Set-up (imports, device start-up, the persistent compile cache at
``.jax_cache/`` in the checkout, the warm-up of the cell's executable) is
timed from the process's start.  The window then runs the cell's traffic
as a closed loop for ``--seconds``.  With ``--trace 0`` the result holds
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
read from the benchmark's spans and the profiler trace.  The last lines
on standard error, and the result's last key ``checks``, give each number
that decides ``correct`` beside its limit.  Exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", type=Path, default=ROOT / ".pimbench_out",
                    help="directory for the run's detailed record")
    args = ap.parse_args(argv)

    import repro  # noqa: F401  the system under test; without it, no run
    from pimbench import harness, spec
    cell = spec.load_cell(args.workload)
    harness.use_compile_cache(ROOT / ".jax_cache")
    import jax
    try:
        harness.require_chips(jax.devices(), cell.chips)
    except harness.NoChip as e:
        print(f"pimbench: {e}; refusing to run", file=sys.stderr)
        return 2
    result = harness.measure(cell, args.seed, args.seconds, bool(args.trace),
                             t0=T0)
    args.out.mkdir(parents=True, exist_ok=True)
    (args.out / f"{cell.name}.seed{args.seed}.trace{args.trace}.json"
     ).write_text(json.dumps(result, indent=1))
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
