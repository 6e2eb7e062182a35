"""Smoke run of the solver service on a TPU: one process, the main path once.

    python chip_smoke.py                  # one chip, mesh2d(512, 512)
    python chip_smoke.py --four-chips     # the one-mesh path on four chips

The one-chip run serves a FEM-mesh-class graph after NACA0015 (Table II
of the pdGRASS paper: 1,039,183 vertices, 3,114,818 edges), cut to a
quarter of that scale: ``mesh2d(512, 512)``, 262,144 vertices and 784,385
edges with weights uniform in [1, 10].  At ``--side 1024`` (the paper's
scale) the cold path does not finish within the smoke's time limit on one
v5e chip: the warmup alone took 1114 s there.  It goes through the entry
points a user calls: ``SolverService.register`` and ``warmup`` (pdGRASS
sparsify, the multilevel hierarchy with device contraction, compilation),
then a ``SolverDaemon`` over that service answering three single-column
requests and one 8-column block, submitted together so the daemon batches
them into one solve.  Every answer is checked against a plain f64 host
reference: ``||b - L_G x|| / ||b||`` with ``L_G`` assembled by
``scipy.sparse`` from the graph's own edge arrays.

``--four-chips`` runs only the mesh path, on all four chips of one host
from this one process: pdGRASS with the distributed recovery engine
against the single-device ``rounds`` engine (identical recovered edge
set), then a solve on ``SolverService(mesh=...)`` against the
single-device service (per-column iterations within 2, the same
solution, the same host residual check), with the placement of every
sharded slab printed.

The lines before the last are one smoke run, not a benchmark.  The last
line is ``{"ok": true, "device": {...}}`` and is printed only when every
check passed on a TPU; without one the script exits non-zero first.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TOL = 1e-5
MAXITER = 4000
RESULT_TIMEOUT_S = 900.0


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def host_laplacian(g):
    """``L_G`` in f64 from the graph's own ``src/dst/weight`` arrays."""
    import scipy.sparse as sp

    w = np.asarray(g.weight, np.float64)
    a = sp.coo_matrix((w, (g.src, g.dst)), shape=(g.n, g.n)).tocsr()
    a = a + a.T
    return (sp.diags(np.asarray(a.sum(axis=1)).ravel()) - a).tocsr()


def host_relres(lap, b, x) -> np.ndarray:
    """Per-column f64 ``||b - L x|| / ||b||`` over the solvable (mean-zero)
    part of ``b``."""
    b = np.asarray(b, np.float64).reshape(lap.shape[0], -1)
    b = b - b.mean(axis=0)
    x = np.asarray(x, np.float64).reshape(lap.shape[0], -1)
    return (np.linalg.norm(b - lap @ x, axis=0)
            / np.linalg.norm(b, axis=0))


def random_rhs(rng, n: int, k: int) -> np.ndarray:
    b = rng.standard_normal((n, k))
    b -= b.mean(axis=0)
    b = b.astype(np.float32)
    return b[:, 0] if k == 1 else b


def _span_ms(tracer, name: str) -> list:
    return [round(d, 1) for d in tracer.durations_ms(name)]


class _SpanPrinter:
    """Prints every span as it finishes (from a thread polling the
    tracer), so a run cut by a time limit still shows how far it got.
    Span times are host times: a span that does not wait on the device
    covers its dispatch and compilation only."""

    def __init__(self, tracer, period_s: float = 2.0):
        import threading

        self._tracer, self._period = tracer, period_s
        self._seen = len(tracer.events())
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _flush(self) -> None:
        events = self._tracer.events()
        for ev in events[self._seen:]:
            if "dur_ns" in ev:
                args = {k: v for k, v in ev.get("args", {}).items()
                        if isinstance(v, (int, float, str))}
                _log(f"  span {ev['name']} {ev['dur_ns'] / 1e6:.1f} ms "
                     f"{args}")
        self._seen = len(events)

    def _run(self) -> None:
        while not self._stop.wait(self._period):
            self._flush()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self._flush()


def one_chip(side: int, seed: int) -> list:
    """Register, warm up and serve four requests through a daemon; returns
    the list of failed checks (empty when all passed)."""
    import jax

    from repro.core.graph import mesh2d
    from repro.obs import get_tracer
    from repro.pipeline import pdgrass_config
    from repro.serve import SolverDaemon
    from repro.solver import SolveRequest, SolverService

    failures = []
    tracer = get_tracer().enable()
    t0 = time.perf_counter()
    g = mesh2d(side, side, seed=seed)
    _log(f"generate mesh2d({side}, {side}): n={g.n} m={g.m} "
         f"{time.perf_counter() - t0:.1f} s")
    lap = host_laplacian(g)

    svc = SolverService(pipeline=pdgrass_config(alpha=0.05),
                        precond="hierarchy")
    _log(f"matvec_impl={svc.matvec_impl} contraction={svc.contraction} "
         f"max_refine={svc.max_refine}")
    t0 = time.perf_counter()
    handle = svc.register(g)
    _log(f"register {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    widths = [1, 1, 1, 8]
    with _SpanPrinter(tracer):
        # the request shapes, and the width of the batch the daemon makes
        # of them, so no compilation lands inside the requests
        svc.warmup(handle, widths=[1, 8, sum(widths)])
    t_warm = time.perf_counter() - t0
    _, (_, _, hier), _ = svc.artifacts(handle)
    sparsify = _span_ms(tracer, "hierarchy.sparsify")
    _log(f"warmup {t_warm:.1f} s: sparsify level 0 {sparsify[0]} ms "
         f"(all levels {round(sum(sparsify), 1)} ms), hierarchy build "
         f"{_span_ms(tracer, 'hierarchy.build')} ms, solve compile "
         f"{round(svc.stats()['timing']['warmup_compile_ms'], 1)} ms")
    for stage in ("pipeline.tree", "pipeline.lifting", "pipeline.scores",
                  "pipeline.grouping", "pipeline.recovery",
                  "hierarchy.contract", "hierarchy.coarse_chol"):
        _log(f"  {stage} per level (ms): {_span_ms(tracer, stage)}")
    _log(f"hierarchy level sizes {hier.level_sizes}")

    rng = np.random.default_rng(seed)
    rhs = [random_rhs(rng, g.n, k) for k in widths]
    with SolverDaemon(svc) as daemon, _SpanPrinter(tracer):
        t0 = time.perf_counter()
        tickets = [daemon.submit(SolveRequest(graph=handle, b=b, tol=TOL,
                                              maxiter=MAXITER))
                   for b in rhs]
        for i, (k, b, ticket) in enumerate(zip(widths, rhs, tickets)):
            try:
                resp = ticket.result(timeout=RESULT_TIMEOUT_S)
            except Exception as e:   # the ticket's group failed: report it
                failures.append(f"request {i} (k={k}): {e!r}")
                continue
            t_req = time.perf_counter() - t0
            rel = host_relres(lap, b, resp.x)
            _log(f"request {i} k={k}: resolved {t_req * 1e3:.1f} ms after "
                 f"submission, iters "
                 f"{resp.iters.tolist()}, refinements {resp.refinements}, "
                 f"service relres max {resp.relres.max():.3e}, host f64 "
                 f"relres max {rel.max():.3e}")
            if not resp.converged:
                failures.append(f"request {i}: not converged")
            if not np.all(rel <= TOL):
                failures.append(f"request {i}: host relres {rel.max():.3e} "
                                f"> tol {TOL}")
    sched = svc.stats()["scheduler"]
    if sched["group_failures"]:
        failures.append(f"group_failures={sched['group_failures']}")
    stats = jax.devices()[0].memory_stats() or {}
    _log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    return failures


def four_chips(side: int, seed: int) -> list:
    """The one-mesh path on four devices against the single-device path;
    returns the list of failed checks."""
    import jax

    from repro.core.graph import mesh2d
    from repro.obs import get_tracer
    from repro.pipeline import Pipeline, pdgrass_config
    from repro.solver import SolverService

    failures = []
    tracer = get_tracer().enable()
    mesh = jax.make_mesh((4,), ("data",))
    g = mesh2d(side, side, seed=seed)
    lap = host_laplacian(g)
    _log(f"mesh2d({side}, {side}): n={g.n} m={g.m}, mesh {mesh.shape}")

    # The distributed engine walks every subtask to its end, so it is
    # compared with the rounds engine run to its end too: with its default
    # early stop at the edge budget, ``rounds`` keeps the top-scored edges
    # of a shorter walk, which is a different (documented) edge set.
    rounds = pdgrass_config(alpha=0.05, stop_at_target=False)
    dist = pdgrass_config(alpha=0.05, engine="distributed")
    t0 = time.perf_counter()
    prep = Pipeline(rounds).prepare(g)
    sp_r = Pipeline(rounds).run(g, prepared=prep)
    t1 = time.perf_counter()
    sp_d = Pipeline(dist).run(g, prepared=prep, mesh=mesh)
    t2 = time.perf_counter()
    same = np.array_equal(sp_r.recovered_mask, sp_d.recovered_mask)
    _log(f"recovery rounds {t1 - t0:.1f} s (with prepare), distributed "
         f"{t2 - t1:.1f} s: recovered {int(sp_r.recovered_mask.sum())} vs "
         f"{int(sp_d.recovered_mask.sum())}, identical edge set: {same}")
    if not same:
        failures.append("distributed recovered edge set != rounds")

    b = random_rhs(np.random.default_rng(seed), g.n, 8)
    t0 = time.perf_counter()
    r1 = SolverService(pipeline=rounds).solve(g, b, tol=TOL,
                                              maxiter=MAXITER)
    t1 = time.perf_counter()
    svc4 = SolverService(pipeline=dist, mesh=mesh)
    r4 = svc4.solve(g, b, tol=TOL, maxiter=MAXITER)
    t2 = time.perf_counter()
    for ev in tracer.events():
        if ev["name"] == "sharded.place":
            for s in ev["args"]["shardings"]:
                _log(f"  placed {s}")
            if any(not s.endswith("on 4 devices")
                   for s in ev["args"]["shardings"]):
                failures.append("a sharded slab is not on all 4 devices")
    d_it = np.abs(r1.iters.astype(np.int64) - r4.iters.astype(np.int64))
    x1 = r1.x - r1.x[0]
    x4 = r4.x - r4.x[0]
    drift = float(np.abs(x1 - x4).max() / max(np.abs(x1).max(), 1e-30))
    rel1, rel4 = host_relres(lap, b, r1.x), host_relres(lap, b, r4.x)
    _log(f"single-device solve {t1 - t0:.1f} s iters {r1.iters.tolist()}; "
         f"4-chip solve {t2 - t1:.1f} s iters {r4.iters.tolist()} "
         f"(contraction={svc4.contraction}); max |diter| {int(d_it.max())}, "
         f"rebased relative drift {drift:.2e}, host relres max "
         f"{rel1.max():.3e} / {rel4.max():.3e}")
    if d_it.max() > 2:
        failures.append(f"iteration counts differ by {int(d_it.max())} > 2")
    if drift > 1e-3:
        failures.append(f"solutions differ: relative drift {drift:.2e}")
    if not (r1.converged and r4.converged):
        failures.append("a solve did not converge")
    if not (np.all(rel1 <= TOL) and np.all(rel4 <= TOL)):
        failures.append("host relres above tol")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh path")
    ap.add_argument("--side", type=int, default=512,
                    help="mesh2d side (vertices = side^2)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    _log(f"device {device}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke run needs one",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if device["count"] < want:
        print(f"chip_smoke: needs {want} chips, found {device['count']}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    _log(f"compilation cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    run = four_chips if args.four_chips else one_chip
    failures = run(args.side, args.seed)
    _log(f"total {time.perf_counter() - t0:.1f} s")
    for f in failures:
        print(f"chip_smoke: FAILED {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
