"""End-to-end driver (the paper's application): sparsifier-preconditioned
Laplacian solve, served through the ``repro.solver`` subsystem's v2 request
plane.

Pipeline per (graph, config), paid once then cached by content hash:
effective-weight spanning tree (Boruvka, JAX) -> binary lifting ->
strict-similarity recovery (round engine) -> SF-GRASS-style multilevel
hierarchy -> jit'd batched device PCG with the hierarchy V-cycle as
preconditioner.  The serving flow is: register the graph once (one O(m)
content hash -> GraphHandle), warm the artifact cache, submit ticket
futures — optionally with per-request PipelineConfig overrides — and flush;
the scheduler batches each (graph, config) group into one device solve.

Single-device here; ``SolverService(mesh=...)`` moves the same request
plane onto a device mesh (row-sharded PCG + V-cycle, mesh-contracted
hierarchy) — see ``examples/distributed_sparsify.py`` for the one-mesh
end-to-end flow.

    PYTHONPATH=src python examples/solve_laplacian.py [--scale medium]
"""
import argparse
import time

import numpy as np

from repro.core import mesh2d, pdgrass
from repro.core.pcg import pcg_host
from repro.launch.compile_cache import enable_compile_cache
from repro.pipeline import fegrass_config, pdgrass_config
from repro.solver import SolveRequest, SolverService


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small",
                    choices=["small", "medium"])
    ap.add_argument("--alpha", type=float, default=0.05)
    ap.add_argument("--batch", type=int, default=8,
                    help="number of right-hand sides per request")
    args = ap.parse_args()
    enable_compile_cache()

    if args.scale == "small":
        g = mesh2d(60, 60, seed=0)
    else:
        g = mesh2d(160, 160, seed=0)
    print(f"graph: |V|={g.n} |E|={g.m}")

    rng = np.random.default_rng(1)
    B = rng.standard_normal((g.n, args.batch)).astype(np.float32)
    B -= B.mean(axis=0)

    # the service takes the full staged pipeline config — any family member
    pd_cfg = pdgrass_config(alpha=args.alpha, chunk=512)
    fe_cfg = fegrass_config(alpha=args.alpha, chunk=512)
    svc = SolverService(pipeline=pd_cfg, precond="hierarchy")

    # register once: the O(m) content hash lives on the handle from here on.
    # warmup builds the hierarchy per config (device propose/accept
    # contraction) AND jit-compiles the solve for the RHS-width bucket, so
    # the first real flush pays neither build nor XLA compile time.
    handle = svc.register(g)
    t0 = time.perf_counter()
    sources = svc.warmup(handle, configs=[pd_cfg, fe_cfg],
                         widths=[args.batch])
    t_warmup = time.perf_counter() - t0
    timing = svc.stats()["timing"]
    print(f"warmup (steps 1-4 + hierarchy + jit per config): "
          f"{t_warmup:.1f} s  artifact sources={sources}  "
          f"compile={timing['warmup_compile_ms']/1e3:.1f} s")

    # one flush, two pipeline configs, one graph: the scheduler splits the
    # pending tickets into per-(graph, config) groups, each a single
    # batched jit'd device PCG against its own cached hierarchy
    t_pd = svc.submit(SolveRequest(graph=handle, b=B))
    t_fe = svc.submit(SolveRequest(graph=handle, b=B, pipeline=fe_cfg))
    t0 = time.perf_counter()
    svc.flush()
    t_flush = time.perf_counter() - t0
    r_pd, r_fe = t_pd.result(), t_fe.result()   # futures, any order
    print(f"mixed flush (compile prepaid by warmup): {t_flush:.1f} s  "
          f"pd: iters={int(r_pd.iters.max())} cache={r_pd.cache}  "
          f"fe: iters={int(r_fe.iters.max())} cache={r_fe.cache}")

    t0 = time.perf_counter()
    warm = svc.solve(handle, B)
    t_warm = time.perf_counter() - t0
    print(f"warm solve (cache hit, jit'd batched PCG): "
          f"{t_warm*1e3:.0f} ms for k={args.batch} RHS "
          f"({t_warm*1e3/args.batch:.1f} ms/rhs)  cache={warm.cache}")
    stats = svc.stats()
    print(f"stats: groups={stats['scheduler']['groups']} "
          f"hash_events={stats['store']['hash_events']} "
          f"solves_by_config={stats['solves_by_config']} "
          f"compile/solve split="
          f"{stats['timing']['warmup_compile_ms']:.0f}/"
          f"{stats['timing']['solve_ms']:.0f} ms")

    # reference: the pre-service path — rebuild the sparsifier and factor it
    # per call, then host PCG (this is what every solve used to cost)
    b0 = B[:, 0].astype(np.float64)
    L = g.laplacian()
    t0 = time.perf_counter()
    sp = pdgrass(g, alpha=args.alpha)
    res_pre = pcg_host(L, b0, sp.laplacian(), tol=1e-5, maxiter=20_000)
    t_host = time.perf_counter() - t0
    print(f"host per-call (pdGRASS rebuild + LU + PCG): {res_pre.iters} "
          f"iters, {t_host*1e3:.0f} ms/rhs")
    xd = warm.x[:, 0] - warm.x[0, 0]
    xh = res_pre.x - res_pre.x[0]
    err = np.abs(xd - xh).max() / max(np.abs(xh).max(), 1.0)
    print(f"device vs host solution: max rel diff {err:.2e} — cached warm "
          f"path speedup {t_host / (t_warm/args.batch):.1f}x per RHS")


if __name__ == "__main__":
    main()
