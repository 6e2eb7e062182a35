"""Solver-service benchmark: per-call host PCG vs cached batched device PCG.

Four ways to serve ``L_G x = b`` traffic on the same graph:

  * ``host``        — the pre-solver-service path: rebuild the pdGRASS
    sparsifier, factor it (sparse LU), and run scipy PCG — per call.
  * ``dev``         — device batched PCG (jit'd lax.while_loop, ELL matvec),
    unpreconditioned, artifacts cached across calls.
  * ``dev+hier:pd`` — device batched PCG preconditioned by the multilevel
    hierarchy built from the **pdGRASS** pipeline config.
  * ``dev+hier:fe`` — the same service, same code path, with the **feGRASS**
    pipeline config as a *per-request override* — the v2 serving API: one
    ``SolverService``, two stage mixes, two cached hierarchies.

The graph is registered once (``svc.register -> GraphHandle``), so the
O(m) content hash is paid once per graph per process — not twice per row
as in the v1 bench.  A final **mixed-config flush** row submits pdGRASS-
and feGRASS-preconditioned requests for the same mesh in one flush; the
scheduler splits them into two (graph, config) groups, each cache-hitting
its own hierarchy.

A **hierarchy-build row** times the multilevel build under both
contraction modes (``host`` sequential greedy matching vs the default
``device`` jit'd propose/accept matching) and asserts they produce the
same chain shape (depth, per-level sizes) — the parity check runs in CI
through ``--quick``.

``--sharded`` adds a **mesh-sharded solve row**: a ``SolverService(mesh=)``
over every visible device (row-sharded PCG + V-cycle + sharded hierarchy
contraction) timed against the same traffic, with solution parity asserted
against the single-device path (re-based solutions within atol, iteration
counts within +-2).  CI runs it under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

    PYTHONPATH=src python benchmarks/solver_bench.py [--scale small] [--k 8]
    PYTHONPATH=src python benchmarks/solver_bench.py --quick
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python benchmarks/solver_bench.py --quick --sharded
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.common import timeit, write_bench_json  # noqa: E402

from repro.core import barabasi_albert, mesh2d, pdgrass  # noqa: E402
from repro.core.pcg import pcg_host  # noqa: E402
from repro.pipeline import fegrass_config, pdgrass_config  # noqa: E402
from repro.solver import (SolveRequest, SolverService,  # noqa: E402
                          build_hierarchy)


def host_solve_per_call(g, b):
    """The old path: steps 1-4 + LU factor + PCG, all rebuilt per call."""
    sp = pdgrass(g, alpha=0.05)
    return pcg_host(g.laplacian(), b.astype(np.float64), sp.laplacian(),
                    tol=1e-5, maxiter=5000)


def mixed_config_flush(svc, handle, B, pd_cfg, fe_cfg):
    """One flush, two PipelineConfigs, same graph: the scheduler must split
    the batch into per-config groups that each hit their cached hierarchy."""
    k = B.shape[1]
    half = max(k // 2, 1)
    t_pd = svc.submit(SolveRequest(graph=handle, b=B[:, :half]))
    t_fe = svc.submit(SolveRequest(graph=handle, b=B[:, half:] if k > 1
                                   else B, pipeline=fe_cfg))
    groups_before = svc.stats()["scheduler"]["groups"]
    t0 = time.perf_counter()
    out = svc.flush()
    t_flush = time.perf_counter() - t0
    groups = svc.stats()["scheduler"]["groups"] - groups_before
    r_pd, r_fe = out[t_pd], out[t_fe]
    assert groups == 2, f"expected 2 (graph, config) groups, got {groups}"
    assert r_pd.config != r_fe.config, "configs collapsed into one group"
    assert r_pd.cache == "mem" and r_fe.cache == "mem", (
        "mixed-config flush missed the artifact cache: "
        f"pd={r_pd.cache} fe={r_fe.cache}")
    assert r_pd.converged and r_fe.converged
    return t_flush, groups


def hierarchy_build_row(name, g, cfg):
    """Time the multilevel hierarchy build under both contraction modes.

    The device path must agree with the host oracle on the chain shape
    (depth + per-level sizes — the strict total order makes the clustering
    identical), so any drift in the propose/accept matching fails the bench
    before it shows up as solver-quality noise.  Device cold includes the
    per-level jit compiles; warm is the serving-relevant rebuild time.
    """
    t0 = time.perf_counter()
    h_host = build_hierarchy(g, config=cfg, contraction="host")
    t_host = time.perf_counter() - t0
    t0 = time.perf_counter()
    h_dev = build_hierarchy(g, config=cfg, contraction="device")
    t_dev_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    h_dev = build_hierarchy(g, config=cfg, contraction="device")
    t_dev = time.perf_counter() - t0
    assert h_dev.depth == h_host.depth, (
        f"{name}: device depth {h_dev.depth} != host {h_host.depth}")
    assert h_dev.level_sizes == h_host.level_sizes, (
        f"{name}: device levels {h_dev.level_sizes} != host "
        f"{h_host.level_sizes}")
    print(f"  hier build:   host={t_host*1e3:8.1f} ms  "
          f"device={t_dev*1e3:8.1f} ms (cold {t_dev_cold*1e3:.1f} ms)  "
          f"depth={h_dev.depth} levels={h_dev.level_sizes}")
    return {"host_ms": t_host * 1e3, "device_ms": t_dev * 1e3,
            "device_cold_ms": t_dev_cold * 1e3, "depth": h_dev.depth,
            "level_sizes": list(h_dev.level_sizes)}


def sharded_solve_row(name, g, B, pd_cfg, ref, repeat=1):
    """Time the mesh-sharded solve plane over every visible device and
    assert solution parity against the single-device path.

    Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to
    exercise real collectives; on one device the mesh is (1,) and the row
    degenerates to a layout check.  Parity contract (same as the tier-1
    suite): re-based solutions within atol, per-column iteration counts
    within +-2.
    """
    import jax

    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    svc = SolverService(pipeline=pd_cfg, mesh=mesh)
    handle = svc.register(g)
    t0 = time.perf_counter()
    cold = svc.solve(handle, B)
    t_cold = time.perf_counter() - t0
    t_warm, warm = timeit(svc.solve, handle, B, repeat=repeat)
    assert warm.cache == "mem" and warm.converged, (name, "sharded")

    def rebase(x):
        x = np.asarray(x, np.float64)
        return x - x[0]

    np.testing.assert_allclose(rebase(warm.x), rebase(ref.x), atol=1e-4,
                               err_msg=f"{name}: sharded solve drifted "
                                       f"from the single-device path")
    d_it = np.abs(np.asarray(warm.iters, np.int64)
                  - np.asarray(ref.iters, np.int64)).max()
    assert d_it <= 2, (
        f"{name}: sharded iteration counts drifted by {d_it} (> 2) "
        f"from the single-device path")
    k = B.shape[1]
    print(f"  sharded ({jax.device_count()} dev) cold={t_cold:6.1f}s  warm="
          f"{t_warm * 1e3 / k:8.2f} ms/rhs   iters={int(warm.iters.max()):<5d}"
          f" relres={float(warm.relres.max()):.1e}  parity_vs_1dev=OK "
          f"(d_iters<={int(d_it)})")
    return {"devices": jax.device_count(), "cold_s": t_cold,
            "warm_ms_per_rhs": t_warm * 1e3 / k,
            "iters": int(warm.iters.max()),
            "relres": float(warm.relres.max()), "d_iters": int(d_it)}


def bench_graph(name, g, k=8, repeat=3, sharded=False):
    rng = np.random.default_rng(0)
    B = rng.standard_normal((g.n, k)).astype(np.float32)
    B -= B.mean(axis=0)

    # host path: one RHS per call (it has no batching), time per call
    t_host, res_host = timeit(host_solve_per_call, g, B[:, 0], repeat=repeat)

    pd_cfg = pdgrass_config(alpha=0.05, chunk=512)
    fe_cfg = fegrass_config(alpha=0.05, chunk=512)
    svc_none = SolverService(pipeline=pd_cfg, precond="none")
    svc_hier = SolverService(pipeline=pd_cfg, precond="hierarchy")
    handle = svc_hier.register(g)   # content hash paid once, reused below
    svc_none.register(handle)
    rows = []
    warm_by_tag = {}
    for tag, svc, pipeline in [
            ("dev", svc_none, None),
            ("dev+hier:pd", svc_hier, None),
            ("dev+hier:fe", svc_hier, fe_cfg)]:
        t0 = time.perf_counter()
        cold = svc.solve(handle, B, pipeline=pipeline)  # build + jit + solve
        t_cold = time.perf_counter() - t0
        t_warm, warm = timeit(svc.solve, handle, B, pipeline=pipeline,
                              repeat=repeat)
        assert warm.cache == "mem" and warm.converged, (name, tag)
        warm_by_tag[tag] = warm
        rows.append({
            "tag": tag,
            "cold_s": t_cold,
            "warm_ms_per_rhs": t_warm * 1e3 / k,
            "iters": int(warm.iters.max()),
            "relres": float(warm.relres.max()),
        })

    host_ms = t_host * 1e3
    print(f"\n{name}: |V|={g.n} |E|={g.m}  batch k={k}")
    hier_rec = hierarchy_build_row(name, g, pd_cfg)
    print(f"  host per-call:        {host_ms:10.1f} ms/rhs   "
          f"iters={res_host.iters}")
    for r in rows:
        speedup = host_ms / r["warm_ms_per_rhs"]
        print(f"  {r['tag']:<12} cold={r['cold_s']:6.1f}s  warm="
              f"{r['warm_ms_per_rhs']:8.2f} ms/rhs   iters={r['iters']:<5d} "
              f"relres={r['relres']:.1e}  speedup_vs_host={speedup:8.1f}x")
    by_tag = {r["tag"]: r for r in rows}
    pd_r, fe_r = by_tag["dev+hier:pd"], by_tag["dev+hier:fe"]
    print(f"  pd-vs-fe (one service, per-request configs): iters "
          f"{pd_r['iters']} vs {fe_r['iters']}, warm "
          f"{pd_r['warm_ms_per_rhs']:.2f} vs "
          f"{fe_r['warm_ms_per_rhs']:.2f} ms/rhs")
    sharded_rec = None
    if sharded:
        sharded_rec = sharded_solve_row(name, g, B, pd_cfg,
                                        warm_by_tag["dev+hier:pd"],
                                        repeat=repeat)
    t_mixed, groups = mixed_config_flush(svc_hier, handle, B, pd_cfg, fe_cfg)
    stats = svc_hier.stats()
    print(f"  mixed flush (pd+fe):  {t_mixed*1e3:8.1f} ms for k={k} RHS in "
          f"{groups} groups  hash_events={stats['store']['hash_events']} "
          f"cache_hits={stats['cache']['hits']}")
    warm_best = min(r["warm_ms_per_rhs"] for r in rows)
    assert warm_best < host_ms, (
        f"{name}: cached device path ({warm_best:.1f} ms/rhs) did not beat "
        f"the per-call host path ({host_ms:.1f} ms/rhs)")
    return {
        "graph": name, "n": g.n, "m": g.m, "k": k,
        "host_ms_per_rhs": host_ms,
        "host_iters": int(res_host.iters),
        "hierarchy_build": hier_rec,
        "rows": rows,
        "sharded": sharded_rec,
        "mixed_flush_ms": t_mixed * 1e3,
        "mixed_flush_groups": groups,
        "convergence": stats["convergence"],
        "speedup_best": host_ms / warm_best,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small",
                    choices=["small", "medium"])
    ap.add_argument("--k", type=int, default=8, help="RHS batch width")
    ap.add_argument("--quick", action="store_true",
                    help="tiny graphs, k=2 — smoke-test the code path")
    ap.add_argument("--sharded", action="store_true",
                    help="add a mesh-sharded solve row over every visible "
                         "device (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 for "
                         "real collectives) asserting parity vs the "
                         "single-device path")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write machine-readable results (schema bench-v1: "
                         "rows, timings, iteration counts, git SHA)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable span tracing for the whole run and export "
                         "a Chrome trace-event JSON (open in "
                         "ui.perfetto.dev)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        from repro.obs import enable_tracing
        enable_tracing()

    if args.quick:
        graphs = {
            "mesh2d-16x16": mesh2d(16, 16, seed=0),
            "ba-300": barabasi_albert(300, 3, seed=1),
        }
        k, repeat = 2, 1
    elif args.scale == "small":
        graphs = {
            "mesh2d-40x40": mesh2d(40, 40, seed=0),
            "mesh2d-60x60": mesh2d(60, 60, seed=0),
            "ba-2000": barabasi_albert(2000, 3, seed=1),
        }
        k, repeat = args.k, 3
    else:
        graphs = {
            "mesh2d-100x100": mesh2d(100, 100, seed=0),
            "mesh2d-160x160": mesh2d(160, 160, seed=0),
            "ba-20000": barabasi_albert(20_000, 3, seed=1),
        }
        k, repeat = args.k, 3

    records = [bench_graph(name, g, k=k, repeat=repeat,
                           sharded=args.sharded)
               for name, g in graphs.items()]
    speedups = [r["speedup_best"] for r in records]
    print(f"\ncached+jit'd device PCG beats the per-call host path on every "
          f"graph (best-path speedups: "
          f"{', '.join(f'{s:.0f}x' for s in speedups)})")
    if args.json:
        write_bench_json(args.json, "solver_bench", records,
                         extra={"quick": args.quick, "scale": args.scale,
                                "k": k, "sharded": args.sharded})
    if args.trace:
        from repro.obs import get_tracer
        get_tracer().export_chrome(args.trace)
        print(f"wrote {args.trace} "
              f"({len(get_tracer().events())} span events)")


if __name__ == "__main__":
    main()
