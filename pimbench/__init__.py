"""On-chip benchmark of the PIM simulator: one cell per run, driven by data.

``BENCHMARK.json`` at the checkout root names the cells.  Each cell joins
a configuration file (``configs/``), a traffic mix (``traffic/<name>.json``),
the pinned simulated statistics (``expected/<cell>.json``), the plain
reference of the workload (``reference/<workload>.py``: its output and
the inputs of each kernel launch), the cell's kernel as text
(``reference/kernels/``) for the plain DPU model (``reference/dpu.py``),
and one reader per metric (``metrics/<metric>.py``), all found by name.
Its CPU tests are in ``tests/pimbench/``.
"""
