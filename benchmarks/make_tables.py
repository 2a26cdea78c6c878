"""Render per-kernel counter rows (``KernelReport.to_row()`` dicts or a
``RunProfile`` JSON's ``kernels`` section) into a markdown table with a
deterministic column order:

    PYTHONPATH=src python benchmarks/make_tables.py --kernels rows.json
"""
from __future__ import annotations

import json
import sys

#: fixed leading columns of the kernel table; every remaining key is
#: appended in sorted order, so two runs always render identical headers
KERNEL_COLUMNS = ("name", "launches", "n_dpus", "n_threads", "cycles",
                  "issued", "ipc", "mram_rd_util", "mram_wr_util",
                  "avg_issuable", "acq_retry", "frac_active",
                  "frac_idle_memory", "frac_idle_revolver", "frac_idle_rf")


def kernel_table(rows):
    """Markdown table of per-kernel counter rows.  Columns come out in
    the fixed :data:`KERNEL_COLUMNS` order (missing keys render ``-``),
    then any extra keys (``mix_*``, workload extras) sorted by name —
    never in dict-insertion order, so diffs between runs are only ever
    about values."""
    extras = sorted({k for r in rows for k in r} - set(KERNEL_COLUMNS))
    cols = [c for c in KERNEL_COLUMNS
            if any(c in r for r in rows)] + extras
    out = ["| " + " | ".join(cols) + " |",
           "|" + "---|" * len(cols)]
    for r in rows:
        out.append("| " + " | ".join(str(r.get(c, "-")) for c in cols)
                   + " |")
    return "\n".join(out)


def load_kernel_rows(path):
    """Kernel rows from a JSON file: either a bare list of ``to_row()``
    dicts or a ``RunProfile`` snapshot (its ``kernels`` section)."""
    with open(path) as f:
        data = json.load(f)
    return data["kernels"] if isinstance(data, dict) else data


if __name__ == "__main__":
    argv = sys.argv[1:]
    if len(argv) != 2 or argv[0] != "--kernels":
        raise SystemExit("usage: make_tables.py --kernels rows.json")
    print("### kernel counters\n")
    print(kernel_table(load_kernel_rows(argv[1])))
