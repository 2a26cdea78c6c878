"""On-chip benchmark of the PIM simulator: one cell per run, driven by data.

``BENCHMARK.json`` at the checkout root names the cells.  Each cell joins
a configuration file (``configs/``), a traffic mix (``traffic/<name>.json``),
the pinned simulated statistics (``expected/<cell>.json``), the plain
reference of the workload (``reference/<workload>.py``: its output and
the inputs of each kernel launch), the plain timing model that the
configuration names (``reference/<timing_model>.py``: ``dpu.py``, which
runs the cell's kernel as text from ``reference/kernels/``, or
``hbm_bank.py``), the control's departure (``control``, for
``control.py``), and one reader per metric (``metrics/<metric>.py``),
all found by name; the warm-up (``warmup/``) follows the backend.
Its CPU tests are in ``tests/pimbench/``.
"""
