"""Device-resident hierarchy build: parity of the jit'd propose/accept
contraction against the sequential host oracle (same clustering, same
coarse Laplacian), the build_hierarchy/SolverService contraction knob,
admission control, and jit-warming warmup()."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (DeviceGraph, barabasi_albert, build_graph, grid2d,
                        mesh2d, star_hub)
from repro.solver import (AdmissionError, SolveRequest, SolverService,
                          build_hierarchy, device_contract, device_matching,
                          ell_laplacian, make_solver)
from repro.solver.hierarchy import contract, heavy_edge_matching


def _rhs(g, k=1, seed=0):
    rng = np.random.default_rng(seed)
    b = rng.standard_normal((g.n, k)).astype(np.float32)
    return b - b.mean(axis=0)


# -- matching / contraction parity -------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: grid2d(12, 12, seed=1),            # road-style
    lambda: mesh2d(14, 14, seed=2),            # FEM-style
    lambda: barabasi_albert(300, 3, seed=3),   # skewed degrees
    lambda: star_hub(250, extra=150, seed=5),  # the degenerate hub input
])
def test_device_contract_matches_host_oracle(make):
    g = make()
    dg = DeviceGraph.from_graph(g)
    np.testing.assert_array_equal(np.asarray(device_matching(dg)),
                                  heavy_edge_matching(g))
    agg_h, coarse_h = contract(g)
    agg_d, coarse_d = device_contract(dg)
    # identical clustering (same strict total order), not merely isomorphic
    np.testing.assert_array_equal(np.asarray(agg_d), agg_h)
    assert (coarse_d.n, coarse_d.m) == (coarse_h.n, coarse_h.m)
    np.testing.assert_array_equal(coarse_d.src, coarse_h.src)
    np.testing.assert_array_equal(coarse_d.dst, coarse_h.dst)
    # weights may differ by f32 summation order only
    np.testing.assert_allclose(coarse_d.weight, coarse_h.weight, rtol=1e-5)
    # every cluster holds >= 2 vertices (a pair, plus absorbed singletons)
    assert coarse_d.n <= g.n // 2
    assert np.all(np.bincount(np.asarray(agg_d)) >= 2)


def test_device_contract_parity_on_exact_weight_ties():
    # uniform weights: the order is decided entirely by the tie-breaks
    g = build_graph(8, [0, 1, 2, 3, 4, 5, 6, 0, 2],
                    [1, 2, 3, 4, 5, 6, 7, 7, 5],
                    np.ones(9, np.float32))
    agg_h, coarse_h = contract(g)
    agg_d, coarse_d = device_contract(DeviceGraph.from_graph(g))
    np.testing.assert_array_equal(np.asarray(agg_d), agg_h)
    np.testing.assert_array_equal(coarse_d.src, coarse_h.src)
    np.testing.assert_array_equal(coarse_d.dst, coarse_h.dst)


def test_device_contract_star_collapses_to_single_vertex():
    # equal-weight pure star: one matched pair, everyone else absorbs into
    # the hub's cluster -> a single coarse vertex with no edges
    n = 12
    g = build_graph(n, np.zeros(n - 1, np.int64), np.arange(1, n),
                    np.ones(n - 1, np.float32))
    agg_d, coarse_d = device_contract(DeviceGraph.from_graph(g))
    agg_h, coarse_h = contract(g)
    assert coarse_d.n == coarse_h.n == 1 and coarse_d.m == 0
    np.testing.assert_array_equal(np.asarray(agg_d), agg_h)


# -- hierarchy knob -----------------------------------------------------------

def test_hierarchy_device_and_host_contraction_agree():
    g = mesh2d(20, 20, seed=9)
    hd = build_hierarchy(g, alpha=0.05, coarse_n=32, contraction="device")
    hh = build_hierarchy(g, alpha=0.05, coarse_n=32, contraction="host")
    assert hd.depth == hh.depth
    assert hd.level_sizes == hh.level_sizes
    for ld, lh in zip(hd.levels, hh.levels):
        np.testing.assert_array_equal(np.asarray(ld.agg), np.asarray(lh.agg))
        assert ld.stats["contraction"] == "device"
        assert lh.stats["contraction"] == "host"
    # spectrally equivalent preconditioners: PCG iterations within +-2
    b = jnp.asarray(_rhs(g, k=2, seed=10))
    idx, val = ell_laplacian(g)
    it = []
    for hier in (hd, hh):
        res = make_solver(idx, val, hierarchy=hier, precond="hierarchy")(
            b, tol=1e-5, maxiter=2000)
        assert bool(np.asarray(res.converged).all())
        it.append(int(np.asarray(res.iters).max()))
    assert abs(it[0] - it[1]) <= 2


def test_hierarchy_device_contraction_handles_hub_graphs():
    g = star_hub(500, extra=300, seed=30)
    hier = build_hierarchy(g, alpha=0.05, coarse_n=64, contraction="device")
    sizes = hier.level_sizes
    assert sizes[-1] <= 64
    for a, b in zip(sizes, sizes[1:]):
        assert b <= a // 2 + 1


def test_contraction_knob_validates():
    g = grid2d(5, 5, seed=0)
    with pytest.raises(ValueError, match="contraction"):
        build_hierarchy(g, contraction="gpu")
    with pytest.raises(ValueError, match="contraction"):
        SolverService(alpha=0.05, contraction="gpu")


def test_contraction_modes_never_share_cache_entries():
    g = grid2d(6, 6, seed=0)
    dev = SolverService(alpha=0.05, contraction="device")
    host = SolverService(alpha=0.05, contraction="host")
    hd, hh = dev.register(g), host.register(g)
    assert dev._key(hd, dev.pipeline) != host._key(hh, host.pipeline)
    assert dev.stats()["hierarchy"]["contraction"] == "device"
    assert host.stats()["hierarchy"]["contraction"] == "host"


# -- admission control ---------------------------------------------------------

def test_admission_rejects_over_budget_submits():
    g = grid2d(6, 6, seed=0)
    svc = SolverService(alpha=0.05, precond="none", max_pending_columns=4)
    b = _rhs(g, k=3, seed=1)
    t1 = svc.submit(SolveRequest(graph=g, b=b))              # 3 columns
    svc.submit(SolveRequest(graph=g, b=b[:, 0]))             # 4th column
    with pytest.raises(AdmissionError) as ei:
        svc.submit(SolveRequest(graph=g, b=b[:, 0]))
    assert (ei.value.pending, ei.value.requested, ei.value.budget) == (4, 1, 4)
    sched = svc.stats()["scheduler"]
    assert sched["submitted"] == 2 and sched["rejected"] == 1
    assert sched["pending_columns"] == 4
    # rejected submits never enter the queue; the rest still solve
    out = svc.flush()
    assert out[t1].converged
    assert svc.stats()["scheduler"]["pending_columns"] == 0


def test_admission_budget_resets_after_flush():
    g = grid2d(6, 6, seed=0)
    svc = SolverService(alpha=0.05, precond="none", max_pending_columns=2)
    b = _rhs(g, k=2, seed=2)
    svc.submit(SolveRequest(graph=g, b=b))
    with pytest.raises(AdmissionError):
        svc.submit(SolveRequest(graph=g, b=b[:, 0]))
    svc.flush()
    assert svc.submit(SolveRequest(graph=g, b=b)).result().converged


def test_unbounded_service_never_rejects():
    g = grid2d(5, 5, seed=0)
    svc = SolverService(alpha=0.05, precond="none")
    for _ in range(8):
        svc.submit(SolveRequest(graph=g, b=_rhs(g, k=4, seed=3)))
    sched = svc.stats()["scheduler"]
    assert sched["rejected"] == 0 and sched["pending_columns"] == 32
    svc.flush()


# -- jit-warming warmup --------------------------------------------------------

def test_warmup_widths_precompile_the_flush_buckets():
    g = mesh2d(10, 10, seed=15)
    svc = SolverService(alpha=0.05)
    h = svc.register(g)
    sources = svc.warmup(h, widths=[1, 3])     # buckets {2, 4}
    assert list(sources.values()) == ["miss"]
    timing = svc.stats()["timing"]
    assert timing["warmup_compile_ms"] > 0
    assert timing["solve_ms"] == 0.0
    key = svc._key(h, svc.pipeline)
    solve = svc._solvers[key]
    if hasattr(solve, "_cache_size"):          # newer jax: assert directly
        compiled = solve._cache_size()
        assert compiled >= 2
    res = svc.solve(h, _rhs(g, k=3, seed=16))  # pads to the warmed 4-bucket
    assert res.converged
    if hasattr(solve, "_cache_size"):
        assert solve._cache_size() == compiled  # no new XLA compilation
    timing = svc.stats()["timing"]
    assert timing["solve_ms"] > 0


def test_warmed_width_one_compiles_nothing_for_a_lone_column():
    """Width 1 warms the width-2 bucket a lone column runs in, so its
    first solve compiles nothing new."""
    g = mesh2d(8, 8, seed=19)
    svc = SolverService(alpha=0.05, precond="none")
    h = svc.register(g)
    svc.warmup(h, widths=[1])
    key = svc._key(h, svc.pipeline)
    assert svc._warmed == {(key, 2)}
    solve = svc._solvers[key]
    compiled = solve._cache_size()
    assert compiled == 1
    assert svc.solve(h, _rhs(g, seed=20)[:, 0]).converged
    assert solve._cache_size() == compiled


def test_rewarm_does_not_inflate_compile_split():
    g = mesh2d(8, 8, seed=18)
    svc = SolverService(alpha=0.05, precond="none")
    h = svc.register(g)
    svc.warmup(h, widths=[2])
    first = svc.stats()["timing"]["warmup_compile_ms"]
    svc.warmup(h, widths=[2])                  # bucket already compiled
    assert svc.stats()["timing"]["warmup_compile_ms"] == first


def test_warmup_without_widths_keeps_v2_contract():
    g = mesh2d(8, 8, seed=17)
    svc = SolverService(alpha=0.05)
    h = svc.register(g)
    assert list(svc.warmup(h).values()) == ["miss"]
    assert list(svc.warmup(h).values()) == ["mem"]
    assert svc.stats()["timing"]["warmup_compile_ms"] == 0.0


def test_warmup_rejects_bad_widths():
    g = grid2d(5, 5, seed=0)
    svc = SolverService(alpha=0.05)
    with pytest.raises(ValueError, match="widths"):
        svc.warmup(g, widths=[0])
