"""Render the dry-run JSON artifacts into the EXPERIMENTS.md roofline table,
plus a measured roofline for the solver hot loop (ELL spmv + V-cycle).

The dry-run tables come from compiled-HLO cost analysis (see
``repro.launch.dryrun``); the solver table instead crosses the analytic
byte/flop models in :mod:`repro.launch.roofline` with *measured* span
timings from the telemetry plane (``solver.solve`` spans).  Its achieved
bytes/s are those of whatever backend runs it, never a device roof's share.

    PYTHONPATH=src python benchmarks/roofline_table.py [--quick]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))


def load(out_dir="experiments"):
    rows = {}
    for f in glob.glob(os.path.join(out_dir, "dryrun_*.json")):
        for r in json.load(open(f)):
            rows[(r["arch"], r["shape"], r["mesh"])] = r
    return sorted(rows.values(), key=lambda r: (r["mesh"], r["arch"],
                                                r["shape"]))


def fmt(x, p=3):
    if x == 0:
        return "0"
    return f"{x:.{p}e}"


def table(rows, mesh):
    lines = [
        "| arch | shape | t_compute (s) | t_memory (s) | t_collective (s) "
        "| bottleneck | args+temp GB/dev | 6ND/2ND / HLO | note |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["mesh"] != mesh:
            continue
        if r["status"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — "
                         f"| — | skipped: sub-quadratic required |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | FAILED | | | | | "
                         f"| {r.get('error','')[:60]} |")
            continue
        mem = r["arg_gb"] + r["temp_gb"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {fmt(r['t_compute'])} "
            f"| {fmt(r['t_memory'])} | {fmt(r['t_collective'])} "
            f"| {r['bottleneck']} | {mem:.1f} | {r['useful_ratio']:.3f} | |")
    return "\n".join(lines)


def solver_table(quick: bool = True):
    """Measured roofline of the solver hot loop.

    One PCG iteration streams: the top-level ELL spmv, one V-cycle over the
    hierarchy's per-level ELL slabs, and ~10 [n, k] vector passes (p/r/z/x
    updates and dot products).  The model bytes cross with the measured
    ``solver.solve`` span (warm, jit-cached) to give achieved bytes/s —
    the iteration count comes from the response's
    convergence telemetry, so nothing here re-runs the solve to count."""
    import numpy as np

    from repro.core import mesh2d
    from repro.launch.roofline import (achieved_bandwidth,
                                       ell_spmv_bytes, ell_spmv_flops,
                                       hierarchy_level_shapes,
                                       hierarchy_level_triples, vcycle_bytes,
                                       vcycle_bytes_fused)
    from repro.obs import get_tracer
    from repro.solver import SolverService

    side, k = (24, 4) if quick else (80, 8)
    g = mesh2d(side, side, seed=0)
    svc = SolverService(alpha=0.05)
    handle = svc.register(g)
    rng = np.random.default_rng(0)
    B = rng.standard_normal((g.n, k)).astype(np.float32)
    B -= B.mean(axis=0)
    svc.solve(handle, B)                    # cold: build artifacts + jit

    tracer = get_tracer()
    was_enabled = tracer.enabled
    tracer.enable()
    tracer.clear()
    warm = svc.solve(handle, B)             # measured, cache + jit warm
    if not was_enabled:
        tracer.disable()

    solve_ms = tracer.durations_ms("solver.solve")
    assert solve_ms, "no solver.solve span recorded — tracer wiring broken"
    _, (idx, val, hier), _ = svc.artifacts(handle)
    l_top = int(idx.shape[1])
    shapes = hierarchy_level_shapes(hier)
    triples = hierarchy_level_triples(hier)
    iters = int(np.asarray(warm.iters).max())

    degree = 2                              # make_vcycle's default smoother
    spmv_b = ell_spmv_bytes(g.n, l_top, k)
    spmv_f = ell_spmv_flops(g.n, l_top, k)
    vc_b = vcycle_bytes(shapes, k, cheby_degree=degree)
    vc_fused_b = vcycle_bytes_fused(triples, k, cheby_degree=degree)
    # acceptance gate: the fused V-cycle must model strictly fewer HBM
    # bytes than the unfused composition on every hierarchy this builds
    assert vc_fused_b < vc_b, (
        f"fused V-cycle byte model ({vc_fused_b}) not below unfused "
        f"({vc_b}) — fusion model regressed")
    vec_b = 10 * g.n * k * 4
    iter_b = spmv_b + vc_b + vec_b
    iter_fused_b = spmv_b + vc_fused_b + vec_b
    total_b = iter_b * max(iters, 1)
    ach = achieved_bandwidth(total_b, solve_ms[0] / 1e3)

    lines = [
        f"solver hot loop: mesh2d-{side}x{side} |V|={g.n} ELL width "
        f"L={l_top} k={k}  hierarchy levels={[s[0] for s in shapes]}",
        "",
        "| component        | bytes/iter (model) | flops/iter (model) |",
        "|---|---|---|",
        f"| ell_spmv (top)   | {spmv_b:>12,} | {spmv_f:>12,} |",
        f"| vcycle (unfused) | {vc_b:>12,} | — |",
        f"| vcycle (fused)   | {vc_fused_b:>12,} | — |",
        f"| vector ops       | {vec_b:>12,} | — |",
        f"| **total/iter**   | {iter_b:>12,} | — |",
        "",
        f"fused V-cycle models {vc_b / vc_fused_b:.2f}x fewer HBM bytes "
        f"than unfused (degree={degree})",
        f"measured: solver.solve span = {solve_ms[0]:.2f} ms, "
        f"iters = {iters}",
    ]
    print("\n".join(lines))
    return {"n": g.n, "k": k, "ell_width": l_top, "iters": iters,
            "bytes_per_iter": iter_b, "bytes_per_iter_fused": iter_fused_b,
            "vcycle_bytes": vc_b, "vcycle_bytes_fused": vc_fused_b,
            "solve_ms": solve_ms[0],
            "achieved_bytes_per_s": ach["bytes_per_s"]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny graph for the solver hot-loop row")
    ap.add_argument("--out-dir", default="experiments",
                    help="directory holding dryrun_*.json artifacts")
    args = ap.parse_args(argv)

    rows = load(args.out_dir)
    for mesh in sorted({r["mesh"] for r in rows}):
        print(f"\n### Mesh {mesh}\n")
        print(table(rows, mesh))
    if not rows:
        print("(no dryrun_*.json artifacts — skipping HLO roofline tables)")

    print("\n### Solver hot loop (measured spans vs analytic model)\n")
    solver_table(quick=args.quick)


if __name__ == "__main__":
    main()
