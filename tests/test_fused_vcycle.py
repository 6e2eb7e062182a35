"""Fused V-cycle kernel suite: parity with the unfused composition.

The fused kernels (`repro.kernels.vcycle_fused`) share the polynomial
definition (`cheby_recurrence`), the einsum contraction, and the
segment-sum with the unfused jnp path, so under interpret mode the two
agree to f32 rounding (the kernels jit separately, so XLA may reassociate
reductions differently — ulp-level, not bitwise).  The serving contract
asserted here: identical PCG iteration counts (±0) across the suite
graphs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.graph import (barabasi_albert, grid2d, mesh2d, star_hub,
                              watts_strogatz)
from repro.kernels.vcycle_fused import (cheby_coeffs, make_fused_chebyshev,
                                        make_fused_restrict_residual,
                                        resolve_interpret)
from repro.pipeline import pdgrass_config
from repro.solver.device_pcg import (ell_laplacian, estimate_dinv_rho,
                                     make_chebyshev_smoother, make_matvec,
                                     make_solver, make_vcycle)
from repro.solver.hierarchy import build_hierarchy


def _suite_graphs():
    return {
        "grid": grid2d(10, 10, seed=1),
        "mesh": mesh2d(10, 10, seed=2),
        "ba": barabasi_albert(150, 3, seed=3),
        "star": star_hub(100, extra=60, seed=5),
    }


_GRAPHS = _suite_graphs()


def _level0(g):
    hier = build_hierarchy(g, config=pdgrass_config(alpha=0.05, chunk=256))
    return hier, hier.levels[0]


def _rhs(n, k, seed=0):
    rng = np.random.default_rng(seed)
    r = rng.standard_normal((n, k)).astype(np.float32)
    r -= r.mean(axis=0)
    return jnp.asarray(r)


# ---------------------------------------------------------------------------
# per-kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(_GRAPHS))
@pytest.mark.parametrize("degree", [2, 3])
def test_fused_smoother_matches_unfused(name, degree):
    g = _GRAPHS[name]
    _, lev = _level0(g)
    mv = make_matvec(lev.idx, lev.val, "ref")
    rho = estimate_dinv_rho(mv, lev.diag)
    smooth_ref = make_chebyshev_smoother(mv, lev.diag, rho, degree=degree)
    smooth_fused = make_fused_chebyshev(lev.idx, lev.val, lev.diag, rho,
                                        degree=degree)
    r = _rhs(lev.n, 4, seed=degree)
    # zero initial iterate (pre-smooth form)
    np.testing.assert_allclose(np.asarray(smooth_fused(r)),
                               np.asarray(smooth_ref(r)),
                               rtol=1e-5, atol=1e-6)
    # warm-start form (post-smooth): z argument threads through
    z0 = _rhs(lev.n, 4, seed=degree + 10) * 0.1
    np.testing.assert_allclose(np.asarray(smooth_fused(r, z0)),
                               np.asarray(smooth_ref(r, z0)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", sorted(_GRAPHS))
def test_fused_restrict_residual_matches_unfused(name):
    g = _GRAPHS[name]
    _, lev = _level0(g)
    mv = make_matvec(lev.idx, lev.val, "ref")
    fused = make_fused_restrict_residual(lev.idx, lev.val, lev.agg,
                                         lev.n_coarse)
    r = _rhs(lev.n, 4, seed=3)
    z = _rhs(lev.n, 4, seed=4) * 0.1
    want = jax.ops.segment_sum(r - mv(z), lev.agg,
                               num_segments=lev.n_coarse)
    np.testing.assert_allclose(np.asarray(fused(r, z)), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tile_n", [32, 64, 256])
@pytest.mark.parametrize("k", [1, 4])
def test_batched_spmv_tile_sweep(tile_n, k):
    g = _GRAPHS["mesh"]
    idx, val = ell_laplacian(g)
    mv_ref = make_matvec(idx, val, "ref")
    mv_fused = make_matvec(idx, val, "fused", tile_n=tile_n)
    x = _rhs(g.n, k, seed=tile_n)
    np.testing.assert_allclose(np.asarray(mv_fused(x)),
                               np.asarray(mv_ref(x)),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# whole-V-cycle parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(_GRAPHS))
@pytest.mark.parametrize("degree", [2, 3])
def test_fused_vcycle_matches_unfused(name, degree):
    g = _GRAPHS[name]
    hier, _ = _level0(g)
    r = _rhs(g.n, 4, seed=degree)
    z_ref = np.asarray(make_vcycle(hier, degree=degree,
                                   matvec_impl="ref")(r))
    z_fused = np.asarray(make_vcycle(hier, degree=degree,
                                     matvec_impl="fused")(r))
    scale = np.abs(z_ref).max()
    np.testing.assert_allclose(z_fused, z_ref, rtol=1e-5,
                               atol=1e-5 * max(scale, 1.0))


@pytest.mark.parametrize("name", sorted(_GRAPHS))
def test_fused_pcg_iteration_counts_identical(name):
    """The serving contract: the fused preconditioner changes HBM traffic,
    not the math — per-column PCG iteration counts match the unfused
    solver exactly (±0)."""
    g = _GRAPHS[name]
    hier, _ = _level0(g)
    idx, val = ell_laplacian(g)
    b = _rhs(g.n, 3, seed=7)
    res_ref = make_solver(idx, val, hierarchy=hier, matvec_impl="ref")(b)
    res_fused = make_solver(idx, val, hierarchy=hier,
                            matvec_impl="fused")(b)
    np.testing.assert_array_equal(np.asarray(res_ref.iters),
                                  np.asarray(res_fused.iters))
    assert bool(np.asarray(res_fused.converged).all())
    # and the solutions agree after re-basing (defined up to a constant)
    x_r = np.asarray(res_ref.x)
    x_f = np.asarray(res_fused.x)
    np.testing.assert_allclose(x_f - x_f[0], x_r - x_r[0],
                               rtol=1e-4, atol=1e-4)


def test_fused_sharded_solver_matches_ref():
    """matvec_impl='fused' on the sharded plane: the per-shard batched
    Pallas contraction must reproduce the jnp shard contraction."""
    from jax.sharding import Mesh

    g = _GRAPHS["mesh"]
    hier, _ = _level0(g)
    idx, val = ell_laplacian(g)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    b = _rhs(g.n, 2, seed=11)
    res_ref = make_solver(idx, val, hierarchy=hier, mesh=mesh,
                          matvec_impl="ref")(b)
    res_fused = make_solver(idx, val, hierarchy=hier, mesh=mesh,
                            matvec_impl="fused")(b)
    np.testing.assert_array_equal(np.asarray(res_ref.iters),
                                  np.asarray(res_fused.iters))
    x_r, x_f = np.asarray(res_ref.x), np.asarray(res_fused.x)
    np.testing.assert_allclose(x_f - x_f[0], x_r - x_r[0],
                               rtol=1e-4, atol=1e-4)


def test_sharded_rejects_kernel_impl():
    from repro.solver.sharded import make_sharded_solver

    with pytest.raises(ValueError, match="fused"):
        make_sharded_solver(jnp.zeros((4, 2), jnp.int32),
                            jnp.zeros((4, 2), jnp.float32),
                            precond="none", mesh=None,
                            matvec_impl="kernel")


# ---------------------------------------------------------------------------
# interpret auto-selection + cache key separation
# ---------------------------------------------------------------------------

def test_resolve_interpret_priority(monkeypatch):
    # an explicit bool wins on every backend
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False
    # None: compiled on a TPU, interpret everywhere else
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_interpret(None) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    # no environment variable can put a kernel into interpret mode on a TPU
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    assert resolve_interpret(None) is False


def test_default_matvec_impl_tracks_interpret(monkeypatch):
    """The default matvec impl no longer follows the interpret resolution:
    it is the declared ``"ref"`` on every backend, TPU included, and the
    service takes it when no impl is named."""
    from repro.solver.device_pcg import default_matvec_impl
    from repro.solver.service import SolverService

    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert default_matvec_impl() == "ref"
        assert SolverService().matvec_impl == "ref"


def test_cheby_coeffs_interval():
    theta, delta, sigma = cheby_coeffs(2.0)
    lmax = 1.1 * 2.0
    assert theta == pytest.approx(0.5 * (lmax + lmax / 4))
    assert delta == pytest.approx(0.5 * (lmax - lmax / 4))
    assert sigma == pytest.approx(theta / delta)


def test_service_key_separates_matvec_impl():
    """matvec_impl joins the artifact fingerprint (schema v7): fused- and
    ref-configured services must never alias cache entries."""
    from repro.solver.service import SolverService

    g = _GRAPHS["grid"]
    svc_ref = SolverService(alpha=0.05, matvec_impl="ref")
    svc_fused = SolverService(alpha=0.05, matvec_impl="fused")
    h_ref = svc_ref.register(g)
    h_fused = svc_fused.register(g)
    k_ref = svc_ref._key(h_ref, svc_ref.pipeline)
    k_fused = svc_fused._key(h_fused, svc_fused.pipeline)
    assert k_ref != k_fused


def test_service_fused_end_to_end():
    """A fused-configured service solves and converges through the full
    request plane (artifacts, jit closure cache, refinement)."""
    from repro.solver.service import SolverService

    g = _GRAPHS["grid"]
    svc = SolverService(alpha=0.05, matvec_impl="fused")
    rng = np.random.default_rng(13)
    b = rng.standard_normal(g.n).astype(np.float32)
    resp = svc.solve(g, b)
    assert resp.converged
    lap = g.laplacian()
    x = np.asarray(resp.x, np.float64)
    bn = np.linalg.norm(b - b.mean())
    assert np.linalg.norm((b - b.mean()) - lap @ x) / bn < 1e-4
