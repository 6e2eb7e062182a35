"""repro.solver tests: hierarchy shape, device PCG numerics parity with the
host solver, preconditioner quality, cache identity, service batching."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import barabasi_albert, grid2d, mesh2d
from repro.core.pcg import pcg_host
from repro.pipeline import pdgrass_config
from repro.solver import (LRUCache, SolveRequest, SolverService, batched_pcg,
                          build_hierarchy, ell_laplacian, graph_fingerprint,
                          make_matvec, make_solver)
from repro.solver.hierarchy import contract, subgraph


def _rhs(g, k=1, seed=0):
    rng = np.random.default_rng(seed)
    b = rng.standard_normal((g.n, k)).astype(np.float32)
    return b - b.mean(axis=0)


def _rebase(x):
    """Laplacian solutions are defined up to a constant; pin x[0] = 0."""
    x = np.asarray(x, dtype=np.float64)
    return x - x[0]


# -- matvec ------------------------------------------------------------------

def test_matvec_kernel_matches_ref_and_scipy():
    g = mesh2d(11, 11, seed=2)
    idx, val = ell_laplacian(g)
    X = jnp.asarray(_rhs(g, k=4, seed=1))
    ref = make_matvec(idx, val, "ref")(X)
    ker = make_matvec(idx, val, "kernel")(X)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    want = g.laplacian() @ np.asarray(X)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=1e-4, atol=1e-4)


# -- device PCG vs host ------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: grid2d(12, 12, seed=1),
    lambda: mesh2d(15, 15, seed=2),
    lambda: barabasi_albert(250, 3, seed=3),
])
def test_device_pcg_matches_host(make):
    g = make()
    b = _rhs(g, k=1, seed=4)
    solve = make_solver(*ell_laplacian(g), precond="none")
    res = solve(jnp.asarray(b), tol=1e-5, maxiter=5000)
    assert bool(np.asarray(res.converged).all())
    assert float(np.asarray(res.relres).max()) <= 1e-5

    host = pcg_host(g.laplacian(), b[:, 0].astype(np.float64),
                    tol=1e-5, maxiter=5000)
    assert host.converged
    # same Krylov method on the same system (projected vs grounded): the
    # iterate counts track each other and the solutions coincide.
    it_dev = int(np.asarray(res.iters)[0])
    assert it_dev <= 2 * host.iters and host.iters <= 2 * it_dev
    xd, xh = _rebase(np.asarray(res.x)[:, 0]), _rebase(host.x)
    scale = max(np.abs(xh).max(), 1.0)
    np.testing.assert_allclose(xd, xh, atol=2e-3 * scale)


def test_batched_pcg_columns_match_single_solves():
    g = mesh2d(13, 13, seed=5)
    idx, val = ell_laplacian(g)
    B = _rhs(g, k=5, seed=6)
    solve = make_solver(idx, val, precond="none")
    res = solve(jnp.asarray(B), tol=1e-5, maxiter=5000)
    for j in range(B.shape[1]):
        one = solve(jnp.asarray(B[:, j:j + 1]), tol=1e-5, maxiter=5000)
        # each column is independent: solving it alone gives the same answer
        np.testing.assert_allclose(_rebase(np.asarray(res.x)[:, j]),
                                   _rebase(np.asarray(one.x)[:, 0]),
                                   atol=1e-3)
        # the same recurrence per column, but XLA may order the f32
        # reductions differently at another block width, so the stopping
        # iteration can move by one
        assert abs(int(np.asarray(res.iters)[j])
                   - int(np.asarray(one.iters)[0])) <= 1


def test_service_converges_where_residual_replacement_stalled():
    """Every column of an 8-RHS request meets tol through the service.

    The PCG loop used to swap its recurrence residual for the f32 product
    ``b - A x`` every 50 iterations without resetting the search
    direction.  That product's own error, about eps * ||A|| ||x||, exceeds
    tol * ||b|| once the system is large or hard enough, and columns then
    stalled at maxiter: on mesh2d(200, 200) with the default hierarchy 7 of
    8 columns, and here (a 60 x 60 mesh whose ~500-vertex coarse level makes
    the stall appear at a size that keeps the test quick) one of 8."""
    g = mesh2d(60, 60, seed=0)
    svc = SolverService(pipeline=pdgrass_config(alpha=0.05, chunk=512),
                        coarse_n=1000)
    B = np.random.default_rng(0).standard_normal((g.n, 8)).astype(np.float32)
    res = svc.solve(g, B, tol=1e-5)
    b = B.astype(np.float64) - B.astype(np.float64).mean(axis=0)
    relres = (np.linalg.norm(b - g.laplacian_matvec(res.x), axis=0)
              / np.linalg.norm(b, axis=0))
    assert res.converged
    assert np.all(relres <= 1e-5), relres
    assert np.all(res.iters < 2000), res.iters


def test_kernel_and_ref_paths_agree_end_to_end():
    g = grid2d(10, 10, seed=7)
    idx, val = ell_laplacian(g)
    b = jnp.asarray(_rhs(g, k=2, seed=8))
    xr = make_solver(idx, val, precond="none", matvec_impl="ref")(b)
    xk = make_solver(idx, val, precond="none", matvec_impl="kernel")(b)
    np.testing.assert_allclose(np.asarray(xr.x), np.asarray(xk.x),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(xr.iters), np.asarray(xk.iters))


# -- hierarchy ---------------------------------------------------------------

def test_hierarchy_levels_shrink_monotonically():
    g = mesh2d(22, 22, seed=9)
    hier = build_hierarchy(g, alpha=0.05, coarse_n=32)
    sizes = hier.level_sizes
    assert sizes[0] == g.n
    assert all(a > b for a, b in zip(sizes, sizes[1:]))
    assert sizes[-1] <= 32
    # every fine level's sparsifier is sparser than its graph, never denser
    for lev in hier.levels:
        assert lev.stats["m_sparsifier"] <= lev.stats["m"]
        assert int(np.asarray(lev.agg).max()) == lev.n_coarse - 1


def test_contract_preserves_connectivity_and_total_weight():
    g = barabasi_albert(200, 3, seed=10)
    sg = subgraph(g, np.ones(g.m, dtype=bool))
    agg, coarse = contract(sg)
    assert coarse.n < g.n
    # cross-cluster weight is conserved (build_graph sums parallel edges)
    cu, cv = agg[g.src], agg[g.dst]
    want = g.weight[cu != cv].sum()
    np.testing.assert_allclose(coarse.weight.sum(), want, rtol=1e-5)


def test_hierarchy_contracts_hub_graphs_without_stalling():
    """Star-like graphs stall pairwise-only matching (one pair per level);
    cluster aggregation must keep the per-level shrink at >= 2x."""
    from repro.core import star_hub

    g = star_hub(500, extra=300, seed=30)
    hier = build_hierarchy(g, alpha=0.05, coarse_n=64)
    sizes = hier.level_sizes
    assert sizes[-1] <= 64
    for a, b in zip(sizes, sizes[1:]):
        assert b <= a // 2 + 1


def test_hierarchy_preconditioner_reduces_iterations():
    g = mesh2d(24, 24, seed=11)
    idx, val = ell_laplacian(g)
    b = jnp.asarray(_rhs(g, k=2, seed=12))
    hier = build_hierarchy(g, alpha=0.05)
    raw = make_solver(idx, val, precond="none")(b, tol=1e-5, maxiter=5000)
    pre = make_solver(idx, val, hierarchy=hier, precond="hierarchy")(
        b, tol=1e-5, maxiter=5000)
    assert bool(np.asarray(pre.converged).all())
    assert int(np.asarray(pre.iters).max()) < int(np.asarray(raw.iters).max())
    np.testing.assert_allclose(_rebase(np.asarray(pre.x)),
                               _rebase(np.asarray(raw.x)), atol=2e-3)


# -- cache -------------------------------------------------------------------

def test_cache_hit_returns_identical_object_without_recompute():
    g = mesh2d(10, 10, seed=13)
    calls = []

    def build():
        calls.append(1)
        return ell_laplacian(g)

    cache = LRUCache(capacity=4)
    key = graph_fingerprint(g, extra=("alpha", 0.05))
    v1, s1 = cache.get_or_build(key, build)
    v2, s2 = cache.get_or_build(key, build)
    assert (s1, s2) == ("miss", "mem")
    assert len(calls) == 1
    assert v1 is v2  # the very same object, no rebuild


def test_fingerprint_distinguishes_graphs_and_params():
    g1 = mesh2d(10, 10, seed=13)
    g2 = mesh2d(10, 10, seed=14)
    assert graph_fingerprint(g1) == graph_fingerprint(g1)
    assert graph_fingerprint(g1) != graph_fingerprint(g2)
    assert graph_fingerprint(g1, ("a", 0.05)) != graph_fingerprint(g1, ("a", 0.1))


def test_cache_lru_eviction_and_disk_tier(tmp_path):
    cache = LRUCache(capacity=2, disk_dir=str(tmp_path))
    for i in range(3):
        cache.put(f"k{i}", i)
    assert len(cache) == 2 and cache.evictions == 1
    # k0 fell out of memory but survives on disk
    v, src = cache.get("k0")
    assert (v, src) == (0, "disk")
    # a fresh cache (new process) hits the disk tier
    v, src = LRUCache(capacity=2, disk_dir=str(tmp_path)).get("k2")
    assert (v, src) == (2, "disk")


def test_service_cache_hit_skips_pipeline(tmp_path):
    g = mesh2d(12, 12, seed=15)
    svc = SolverService(alpha=0.05, disk_dir=str(tmp_path))
    b = _rhs(g, k=1, seed=16)[:, 0]
    r1 = svc.solve(g, b)
    r2 = svc.solve(g, b)
    assert (r1.cache, r2.cache) == ("miss", "mem")
    assert svc.cache.stats["misses"] == 1 and svc.cache.stats["hits"] == 1
    np.testing.assert_array_equal(r1.x, r2.x)  # same artifacts, same answer
    # a new service instance warm-starts from disk
    r3 = SolverService(alpha=0.05, disk_dir=str(tmp_path)).solve(g, b)
    assert r3.cache == "disk"
    np.testing.assert_allclose(_rebase(r3.x), _rebase(r1.x), atol=1e-4)


# -- service -----------------------------------------------------------------

def test_service_solution_matches_host_pcg():
    g = mesh2d(14, 14, seed=17)
    b = _rhs(g, k=1, seed=18)[:, 0]
    svc = SolverService(alpha=0.05)
    res = svc.solve(g, b, tol=1e-5)
    assert res.converged
    assert float(res.relres.max()) <= 1e-5
    host = pcg_host(g.laplacian(), b.astype(np.float64), tol=1e-5,
                    maxiter=5000)
    scale = max(np.abs(host.x).max(), 1.0)
    np.testing.assert_allclose(_rebase(res.x), _rebase(host.x),
                               atol=2e-3 * scale)


def test_service_flush_groups_requests_into_one_batch():
    g = mesh2d(12, 12, seed=19)
    svc = SolverService(alpha=0.05)
    b1 = _rhs(g, k=1, seed=20)[:, 0]
    b2 = _rhs(g, k=3, seed=21)
    t1 = svc.submit(SolveRequest(graph=g, b=b1))
    t2 = svc.submit(SolveRequest(graph=g, b=b2))
    out = svc.flush()
    assert out[t1].x.shape == (g.n,)
    assert out[t2].x.shape == (g.n, 3)
    assert out[t1].converged and out[t2].converged
    # both tickets were served by the same artifact build (one group)
    assert svc.cache.stats["misses"] == 1
    single = svc.solve(g, b2[:, 1])
    np.testing.assert_allclose(_rebase(out[t2].x[:, 1]), _rebase(single.x),
                               atol=1e-3)


def test_solve_does_not_drain_submitted_tickets():
    g = mesh2d(10, 10, seed=24)
    svc = SolverService(alpha=0.05)
    b = _rhs(g, k=2, seed=25)
    ticket = svc.submit(SolveRequest(graph=g, b=b[:, 0]))
    direct = svc.solve(g, b[:, 1])       # must not consume the queue
    assert direct.converged
    out = svc.flush()
    assert ticket in out and out[ticket].converged
    np.testing.assert_allclose(
        _rebase(out[ticket].x),
        _rebase(svc.solve(g, b[:, 0]).x), atol=1e-3)


def test_mixed_tolerances_keep_their_own_contracts():
    g = mesh2d(10, 10, seed=26)
    svc = SolverService(alpha=0.05)
    b = _rhs(g, k=2, seed=27)
    loose = svc.submit(SolveRequest(graph=g, b=b[:, 0], tol=1e-2))
    strict = svc.submit(SolveRequest(graph=g, b=b[:, 1], tol=1e-5))
    out = svc.flush()
    assert out[loose].converged and float(out[loose].relres.max()) <= 1e-2
    assert out[strict].converged and float(out[strict].relres.max()) <= 1e-5


def test_mixed_maxiter_budgets_are_honored_per_request():
    g = mesh2d(10, 10, seed=31)
    svc = SolverService(alpha=0.05, precond="none")
    b = _rhs(g, k=2, seed=32)
    small = svc.submit(SolveRequest(graph=g, b=b[:, 0], maxiter=5))
    large = svc.submit(SolveRequest(graph=g, b=b[:, 1], maxiter=5000))
    out = svc.flush()
    assert int(out[small].iters.max()) <= 5 and not out[small].converged
    assert out[large].converged


def test_service_rejects_mismatched_rhs():
    g = grid2d(6, 6, seed=28)
    svc = SolverService(alpha=0.05)
    with pytest.raises(ValueError, match="does not match graph"):
        svc.solve(g, np.ones(g.n + 1, np.float32))


def test_solver_closures_bounded_by_cache_capacity():
    svc = SolverService(alpha=0.05, precond="none", cache_capacity=2)
    rng = np.random.default_rng(29)
    for s in range(4):
        g = grid2d(6, 6, seed=s)
        b = rng.standard_normal(g.n).astype(np.float32)
        assert svc.solve(g, b - b.mean()).converged
    assert len(svc._solvers) <= 2


def test_batched_pcg_handles_zero_columns():
    g = grid2d(8, 8, seed=22)
    idx, val = ell_laplacian(g)
    B = np.zeros((g.n, 2), np.float32)
    B[:, 0] = _rhs(g, k=1, seed=23)[:, 0]
    mv = make_matvec(idx, val, "ref")
    res = batched_pcg(mv, jnp.asarray(B), tol=1e-5, maxiter=2000)
    assert bool(np.asarray(res.converged).all())
    assert int(np.asarray(res.iters)[1]) == 0  # zero RHS converges instantly
