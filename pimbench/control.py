#!/usr/bin/env python3
"""Readings for the limits of the check: sound runs and the control.

    python3 pimbench/control.py --workload rank64.va --seconds 5 \\
        --sound 11 12 13 --control 21 22 23

In one process, each ``--sound`` seed runs the cell's closed loop for
``--seconds`` as configured, and each ``--control`` seed runs it with the
control: the departure from the configuration that the configuration
file names under ``control``, read by its own timing model; where it
names none, the DPU's revolver distance cut from 11 cycles to 10.  Such
a departure still computes right answers, but at other cycle counts:
the step a change for speed might take by accident.  Prints one JSON
line per seed with ``correct`` and each number compared.  The
benchmark's own runs never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: the control's departure from a configuration that names none
CONTROL = {"revolver_cycles": 10}


def departure(cell) -> dict:
    """The control's departure from the cell's configuration."""
    return cell.config.get("control", CONTROL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--sound", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from pimbench import harness, spec
    harness.use_compile_cache(ROOT / ".jax_cache")
    cell = spec.load_cell(args.workload)
    runs = ([("sound", s, None) for s in args.sound]
            + [("control", s, departure(cell)) for s in args.control])
    for kind, seed, override in runs:
        r = harness.measure(cell, seed, args.seconds, False, t0=T0,
                            dpu_override=override, log=lambda _: None)
        print(json.dumps({"kind": kind, "seed": seed,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "device": r["device"]["kind"],
                          "checks": {k: v["value"]
                                     for k, v in r["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
