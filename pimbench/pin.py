#!/usr/bin/env python3
"""Pin the simulated statistics of a cell's pool of data seeds.

    python3 pimbench/pin.py --workload rank64.bfs [--data-seeds 0 1] [--write]

Runs each data seed once through ``Workload.run`` and prints one JSON line
per seed: the statistics ``check.py`` compares, and whether the output
matched the plain reference.  ``--write`` merges them into
``expected/<cell>.json``; where the configuration's timing model runs a
kernel file (``dpu``), it also writes the cell's kernel, as the program
assembles it, to ``reference/kernels/<workload>.t<tasklets>.txt``.
Runs on any platform: the statistics are meant to be the same on the
CPU and on the chip.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def write_kernel(prog) -> Path:
    """The cell's kernel as text: one instruction per line, its name,
    rd, ra, rb, imm and use_imm."""
    from repro.core.isa import Op
    t = prog.cfg.n_tasklets
    b = prog.workload.build(t).binary(prog.cfg.iram_instrs)
    path = (ROOT / "pimbench" / "reference" / "kernels"
            / f"{prog.traffic['workload']}.t{t}.txt")
    path.write_text("".join(
        f"{Op(int(b.opcode[i])).name} {b.rd[i]} {b.ra[i]} {b.rb[i]} "
        f"{b.imm[i]} {b.use_imm[i]}\n" for i in range(b.n_instrs)))
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--data-seeds", type=int, nargs="*")
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from pimbench import check, harness, spec
    cell = spec.load_cell(args.workload)
    prog = harness.Program(cell)
    prog.prewarm()
    seeds = args.data_seeds
    if seeds is None:
        seeds = range(int(cell.traffic["seed_pool"]))
    ref = cell.reference()
    pinned = {}
    for ds in seeds:
        sim = harness.Sim(data_seed=ds)
        prog.simulate(sim)
        if sim.error:
            print(json.dumps({"data_seed": ds, "error": sim.error}))
            return 1
        wrong = check.words_wrong(
            sim.image, ref.image(prog.dpu, cell.traffic["sizes"], ds))
        print(json.dumps({"data_seed": ds,
                          "platform": jax.devices()[0].platform,
                          **sim.stats, "output_words_wrong": wrong}),
              flush=True)
        if wrong:
            return 1
        pinned[str(ds)] = sim.stats
    if args.write:
        if hasattr(cell.timing_model(), "kernel"):   # it reads a kernel file
            write_kernel(prog)
        path = cell.expected_path()
        doc = (json.loads(path.read_text()) if path.exists()
               else {"data_seeds": {}})
        doc["data_seeds"].update(pinned)
        doc["data_seeds"] = dict(sorted(doc["data_seeds"].items(),
                                        key=lambda kv: int(kv[0])))
        doc["platform"] = jax.devices()[0].platform
        path.write_text(json.dumps(doc, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
