"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per figure/design point).
``--scale`` grows datasets toward the paper's Table II sizes; default runs
the suite at CI scale in a few minutes.  ``--suite`` selects a family
(``figs`` paper figures, ``comm`` interconnect/collectives, ``overlap``
async-pipeline, ``faults`` fault-injection availability/goodput,
``cluster`` multi-tenant cluster runtime, ``all``); ``--only`` further
filters by substring — a filter matching nothing is an error listing
the valid bench names, not a silent no-op.
A bench that raises prints an ``error`` row, and the run exits non-zero
once every selected bench has run.

``--trace PATH`` runs the selected benches under a process-wide
:class:`repro.obs.Tracer` (every :class:`PIMSystem` any suite builds
attaches automatically) and writes the combined Chrome-trace JSON to
PATH plus a ``RunProfile`` counters snapshot next to it
(``<PATH minus .json>.counters.json``) — open the trace in
``ui.perfetto.dev``, render the counters with ``python -m
repro.obs.report``.  ``--check`` (requires ``--trace``) gates on
trace/timeline consistency: every system's per-phase span sums must
match its timeline busy totals, or the run exits nonzero.

    PYTHONPATH=src python -m benchmarks.run [--scale 0.05] \\
        [--suite comm] [--only fig11] [--trace run.trace.json] [--check]
"""
from __future__ import annotations

import argparse
import json
import os
import time

#: suite families selectable via --suite (benches declare theirs inline)
SUITE_NAMES = ("figs", "comm", "overlap", "faults", "cluster", "overload",
               "pathfind")


def _emit(name: str, wall_s: float, rows):
    derived = json.dumps(rows, default=float)
    print(f"{name},{wall_s * 1e6:.0f},{derived}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--suite", default="all",
                    choices=("all",) + SUITE_NAMES)
    ap.add_argument("--only", default=None)
    ap.add_argument("--list", action="store_true",
                    help="print every registered bench (grouped by suite) "
                         "and exit without running anything")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of the run to PATH "
                         "(plus a RunProfile counters snapshot next to it)")
    ap.add_argument("--check", action="store_true",
                    help="with --trace: fail unless every system's "
                         "per-phase span sums match its timeline totals")
    args = ap.parse_args()
    if args.check and not args.trace:
        ap.error("--check requires --trace")
    from repro.core import compile_cache
    compile_cache.use_persistent_cache()

    tracer = profile = None
    if args.trace:
        from repro import obs
        tracer = obs.Tracer()
        obs.set_default_tracer(tracer)
        # construct before the benches run: the compile-cache baseline is
        # taken here, so the snapshot reports this run's delta
        profile = obs.RunProfile(name=f"bench:{args.suite}")

    from benchmarks import cluster_load, comm_scaling, fault_tolerance, \
        overlap_scaling, overload, pathfind_arch, pim_figs, rank_overlap, \
        trace_replay

    char = None

    def need_char():
        nonlocal char
        if char is None:
            char = pim_figs.characterize(args.scale)
        return char

    # single registry: bench name -> (suite, thunk, standalone caps) —
    # caps are the flags the bench's OWN script supports when run
    # directly (python benchmarks/<module>.py --smoke/--check), shown
    # by --list so CI wiring is discoverable
    benches = {
        "fig5_util": ("figs", lambda: pim_figs.fig5_utilization(need_char(), args.scale), ()),
        "fig6_breakdown": ("figs", lambda: pim_figs.fig6_breakdown(need_char(), args.scale), ()),
        "fig7_tlp_hist": ("figs", lambda: pim_figs.fig7_tlp_hist(need_char(), args.scale), ()),
        "fig8_tlp_ts": ("figs", lambda: pim_figs.fig8_tlp_timeseries(need_char(), args.scale), ()),
        "fig9_instr_mix": ("figs", lambda: pim_figs.fig9_instr_mix(need_char(), args.scale), ()),
        "fig10_scaling": ("figs", lambda: pim_figs.fig10_strong_scaling(args.scale), ()),
        "comm_scaling": ("comm", lambda: comm_scaling.comm_strong_scaling(args.scale), ()),
        "comm_micro": ("comm", lambda: comm_scaling.collective_microbench(args.scale), ()),
        "overlap_scaling": ("overlap", lambda: overlap_scaling.overlap_strong_scaling(args.scale), ()),
        "overlap_depth": ("overlap", lambda: overlap_scaling.overlap_depth_sweep(args.scale), ()),
        "rank_overlap": ("overlap", lambda: rank_overlap.rank_overlap(args.scale), ()),
        "rank_contention": ("overlap", lambda: rank_overlap.contention_sweep(args.scale), ()),
        "rank_calibration": ("overlap", lambda: rank_overlap.contention_calibration(args.scale), ()),
        "fig11_simt": ("figs", lambda: pim_figs.fig11_simt(args.scale), ()),
        "fig12_ilp": ("figs", lambda: pim_figs.fig12_ilp(args.scale), ()),
        "fig13_mram_bw": ("figs", lambda: pim_figs.fig13_mram_bw(args.scale), ()),
        "fig15_cache": ("figs", lambda: pim_figs.fig15_cache_vs_scratchpad(args.scale), ()),
        "mmu_overhead": ("figs", lambda: pim_figs.mmu_overhead(args.scale), ()),
        "simulation_rate": ("figs", lambda: pim_figs.simulation_rate(args.scale), ()),
        "fault_smoke": ("faults", lambda: [fault_tolerance.smoke()],
                        ("--smoke", "--check")),
        "fault_tolerance": ("faults", lambda: fault_tolerance.sweep(
            args.scale, rates=[0.0, 0.02, 0.05], trials=2, launches=4),
            ("--smoke", "--check")),
        "cluster_smoke": ("cluster", lambda: [cluster_load.smoke()],
                          ("--smoke", "--check")),
        "cluster_load": ("cluster", lambda: cluster_load.load_table(
            args.scale), ("--smoke", "--check")),
        "overload_chaos": ("overload", lambda: overload.chaos_table(
            args.scale), ("--smoke", "--check")),
        "overload_hedge": ("overload", lambda: overload.hedge_rows(
            args.scale), ("--smoke", "--check")),
        "overload_resume": ("overload", lambda: [overload.smoke()],
                            ("--smoke", "--check")),
        "pathfind_arch": ("pathfind", lambda: pathfind_arch.compare(
            args.scale), ()),
        "pathfind_replay_sweep": ("pathfind",
                                  lambda: pathfind_arch.replay_sweep(
                                      args.scale), ()),
        "trace_replay_smoke": ("pathfind", lambda: [trace_replay.smoke(
            args.scale)], ("--check",)),
    }
    bad = {k for k, (s, _, _) in benches.items() if s not in SUITE_NAMES}
    assert not bad, f"benches with unknown suite: {bad}"
    if args.list:
        for suite in SUITE_NAMES:
            members = sorted(k for k, (s, _, _) in benches.items()
                             if s == suite)
            print(f"{suite}:")
            for name in members:
                caps = benches[name][2]
                suffix = f"  [{' '.join(caps)}]" if caps else ""
                print(f"  {name}{suffix}")
        return
    selected = {k: fn for k, (suite, fn, _) in benches.items()
                if args.suite in ("all", suite)}
    if args.only:
        selected = {k: v for k, v in selected.items() if args.only in k}
    if not selected:
        # a typo'd --only used to "run" zero benches and exit 0 — make it
        # an error that names what would have matched
        valid = ", ".join(sorted(benches))
        raise SystemExit(
            f"no benchmark matches --suite {args.suite!r}"
            + (f" --only {args.only!r}" if args.only else "")
            + f"; valid names: {valid}")

    failed = []
    for name, fn in selected.items():
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            # keep going so every bench reports; the run fails below
            rows = [{"error": f"{type(e).__name__}: {e}"}]
            failed.append(name)
        _emit(name, time.time() - t0, rows)

    if tracer is not None:
        tracer.finalize()
        tracer.save(args.trace)
        for system in tracer.systems:
            profile.record_system(system)
        profile.record_compile_cache()
        counters_path = os.path.splitext(args.trace)[0] + ".counters.json"
        profile.save(counters_path)
        print(f"# trace: {args.trace}  counters: {counters_path}")
        if args.check:
            errors = tracer.validate()
            if errors:
                raise SystemExit("trace/timeline mismatch:\n"
                                 + "\n".join(errors))
            print(f"# check: OK ({len(tracer.systems)} systems consistent)")
    if failed:
        raise SystemExit(f"{len(failed)} bench(es) failed: "
                         + ", ".join(failed))


if __name__ == "__main__":
    main()
