"""Benchmark harness entry point — one module per paper table/figure.

  table2_quality  -> Table II  (recovery runtime, passes, PCG iters;
                     pdGRASS vs feGRASS through one Pipeline code path)
  table3_jbp      -> Table III (Judge-Before-Parallel statistics)
  table4_scaling  -> Table IV / Figs 6-8 (strong scaling, work-span)
  fig1_summary    -> Figure 1  (relative time/quality ratios)
  pdgrass_perf    -> §Perf     (recovery-engine hillclimbing)
  kernels_bench   -> Pallas kernel shape sweep (interpret mode on CPU)
  solver_bench    -> solver service vs per-call host path
  spectral_bench  -> batched resistance queries + embedding workloads
  analysis       -> static invariant checkers (zero findings asserted)

Prints ``name,us_per_call,derived`` CSV per section; roofline terms for
the (arch x shape) cells come from ``repro.launch.dryrun`` artifacts and
are summarized in EXPERIMENTS.md.

``--smoke`` forwards ``--quick`` to every section: tiny graphs, seconds
per section — CI runs this to catch API drift in code paths the tier-1
tests never import.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# allow `python benchmarks/run.py` without the repo root on PYTHONPATH
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="run every section with --quick on tiny graphs")
    ap.add_argument("--skip", action="append", default=[],
                    help="section name to skip (repeatable) — e.g. CI runs "
                         "solver_bench as its own fail-fast step")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write machine-readable harness results "
                         "(per-section runtimes + embedded solver_bench "
                         "detail, schema bench-v1 with git SHA)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable span tracing for the whole run and export "
                         "a Chrome trace-event JSON")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (analysis_bench, fig1_summary, kernels_bench,
                            pdgrass_perf, replay_bench, solver_bench,
                            spectral_bench, table2_quality, table3_jbp,
                            table4_scaling)
    from benchmarks.common import write_bench_json

    if args.trace:
        from repro.obs import enable_tracing
        enable_tracing()

    sections = [
        ("table2_quality", table2_quality.main),
        ("table3_jbp", table3_jbp.main),
        ("table4_scaling", table4_scaling.main),
        ("fig1_summary", fig1_summary.main),
        ("pdgrass_perf", pdgrass_perf.main),
        ("kernels_bench", kernels_bench.main),
        ("solver_bench", solver_bench.main),
        ("replay_bench", replay_bench.main),
        ("spectral_bench", spectral_bench.main),
        ("analysis", analysis_bench.main),
    ]
    section_argv = ["--quick"] if args.smoke else []
    solver_json = kernels_json = analysis_json = None
    if args.json:
        # solver_bench / kernels_bench / analysis write their own detail
        # records; embed them in ours
        solver_json = args.json + ".solver_bench.tmp"
        kernels_json = args.json + ".kernels_bench.tmp"
        analysis_json = args.json + ".analysis.tmp"
    section_runtimes = {}
    for name, fn in sections:
        if name in args.skip:
            print(f"\n=== {name} === (skipped)")
            continue
        print(f"\n=== {name} ===")
        extra_argv = []
        if solver_json and name == "solver_bench":
            extra_argv = ["--json", solver_json]
        elif kernels_json and name == "kernels_bench":
            extra_argv = ["--json", kernels_json]
        elif analysis_json and name == "analysis":
            extra_argv = ["--json", analysis_json]
        t0 = time.perf_counter()
        fn(section_argv + extra_argv)
        dt = time.perf_counter() - t0
        section_runtimes[name] = dt
        print(f"# section_runtime,{dt*1e6:.0f},{name}")

    if args.json:
        import json as json_mod

        def _take(tmp_path):
            if tmp_path and os.path.exists(tmp_path):
                with open(tmp_path) as f:
                    detail = json_mod.load(f)
                os.remove(tmp_path)
                return detail
            return None

        write_bench_json(
            args.json, "run",
            {"section_runtimes_s": section_runtimes,
             "skipped": args.skip, "solver_bench": _take(solver_json),
             "kernels_bench": _take(kernels_json),
             "analysis": _take(analysis_json)},
            extra={"smoke": args.smoke})
    if args.trace:
        from repro.obs import get_tracer
        get_tracer().export_chrome(args.trace)
        print(f"wrote {args.trace} "
              f"({len(get_tracer().events())} span events)")


if __name__ == "__main__":
    main()
