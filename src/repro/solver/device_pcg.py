"""Fully jit'd batched-RHS PCG on the device, preconditioned by the hierarchy.

This replaces the per-call host loop of ``core/pcg.py`` for the serving
path: one ``lax.while_loop`` advances all ``k`` right-hand sides of a
``[n, k]`` batch simultaneously (per-column alpha/beta, converged columns
frozen), and the matvec routes through the Pallas ELL kernel
(``kernels/spmv_ell.py``) or a pure-``jnp`` reference path with identical
numerics.

The Laplacian is singular (nullspace = constants), so instead of grounding
a vertex (which reshuffles indices) the solve stays in ``range(L)``: the
right-hand sides are centered and every preconditioner output is centered.
Solutions are determined up to a constant; compare against the host solver
after re-basing (``x - x[0]``).

The hierarchy preconditioner is a symmetric V(1,1)-cycle over the
:class:`repro.solver.hierarchy.Hierarchy` chain: a forward sweep down the
aggregation tree (Chebyshev polynomial smooth + residual restriction), a
tiny dense Cholesky solve at the coarsest level, and a backward sweep up
(prolongation + smooth).  The smoother is a degree-2/3 Chebyshev polynomial
in the Jacobi-preconditioned operator ``D^-1 L`` targeting the upper part
of its spectrum, with the spectral radius estimated per level by a cheap
power iteration at closure-build time — no ``omega`` to tune, and equal or
fewer PCG iterations than the weighted-Jacobi smoother it replaced.  The
polynomial is a fixed symmetric operator, so pre/post-smoothing with the
same polynomial keeps the V-cycle SPD on the mean-zero subspace, which PCG
requires.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.vcycle_fused import (cheby_coeffs, cheby_recurrence,
                                        ell_contract, make_fused_chebyshev,
                                        make_fused_restrict_residual)
from repro.obs.device import named_scope
from repro.solver.hierarchy import Hierarchy


class BatchedPCGResult(NamedTuple):
    x: jnp.ndarray        # [n, k] mean-zero solutions
    iters: jnp.ndarray    # [k] int32 per-column iteration counts
    relres: jnp.ndarray   # [k] true relative residuals ||b - Lx|| / ||b||
    converged: jnp.ndarray  # [k] bool


def default_matvec_impl() -> str:
    """The solve plane's declared matvec impl: ``"ref"`` on every backend.

    It is the one path the TPU compiler accepts today — Mosaic refuses the
    Pallas kernels' in-kernel gathers (``tests/test_tpu_compile.py``) — and
    a fixed default means the CPU tests run exactly the code the chip
    runs.  ``"fused"`` and ``"kernel"`` stay explicit choices."""
    return "ref"


def ell_laplacian(graph):
    """ELL slabs of a Graph's Laplacian (thin alias kept here so solver
    consumers never import the kernels package directly)."""
    return ops.to_ell(graph)


def make_matvec(idx, val, impl: str = "ref", tile_n: int = 256,
                interpret: Optional[bool] = None) -> Callable:
    """Batched ELL matvec ``[n, k] -> [n, k]``.

    ``impl="fused"`` routes the whole ``[n, k]`` block through the
    batched-RHS Pallas kernel (one dispatch, x VMEM resident);
    ``impl="kernel"`` unrolls the (static, small) column dimension through
    the single-column Pallas kernel; ``impl="ref"`` is the one-gather jnp
    path.  All compute y[i, j] = sum_l val[i, l] * x[idx[i, l], j].
    """
    if impl == "fused":
        def matvec(x):
            return ops.spmv_batched(idx, val, x, tile_n=tile_n,
                                    interpret=interpret)
    elif impl == "kernel":
        def matvec(x):
            cols = [ops.spmv(idx, val, x[:, j], tile_n=tile_n,
                             interpret=interpret)
                    for j in range(x.shape[1])]
            return jnp.stack(cols, axis=1)
    elif impl == "ref":
        def matvec(x):
            return ell_contract(idx, val, x)
    else:
        raise ValueError(f"unknown matvec impl {impl!r}")
    return matvec


def _center(x):
    return x - jnp.mean(x, axis=0, keepdims=True)


def estimate_dinv_rho_device(matvec: Callable, diag, iters: int = 12):
    """Power-iteration estimate of ``rho(D^-1 L)`` as a DEVICE scalar.

    Deterministic start vector, ~``iters`` gather/scatter sweeps; no host
    sync — callers that estimate several levels (the V-cycle builders)
    batch all estimates into one ``jax.device_get`` instead of blocking
    once per level.  The constant nullspace has eigenvalue 0 and decays
    under iteration, so no explicit projection is needed.
    """
    n = diag.shape[0]
    v = jnp.sin(jnp.arange(n, dtype=jnp.float32) * 1.7 + 0.3)
    v = v / jnp.linalg.norm(v)
    d = diag

    def body(_, v):
        w = matvec(v[:, None])[:, 0] / d
        return w / jnp.maximum(jnp.linalg.norm(w), jnp.float32(1e-30))

    v = jax.lax.fori_loop(0, iters, body, v)
    w = matvec(v[:, None])[:, 0] / d
    return jnp.linalg.norm(w)


def estimate_dinv_rho(matvec: Callable, diag, iters: int = 12) -> float:
    """Host-scalar convenience over :func:`estimate_dinv_rho_device` for
    single-level callers (tests, benchmarks).  Runs once per level at
    closure-build time, so the designated sync below is amortized over
    every solve the closure serves."""
    return float(jax.device_get(estimate_dinv_rho_device(matvec, diag,
                                                         iters)))


def make_chebyshev_smoother(matvec: Callable, diag, rho: float,
                            degree: int = 3) -> Callable:
    """Degree-``degree`` Chebyshev smoother for ``L z = r`` with Jacobi
    scaling, targeting eigenvalues of ``D^-1 L`` in ``[lmax/4, lmax]``
    (``lmax = 1.1 * rho`` for safety — overestimating is benign,
    underestimating can amplify the top mode).  The upper-quarter band is
    the classic smoothing choice: the coarse correction owns the low modes,
    so the polynomial concentrates its damping where aggregation cannot
    reach.

    Returns ``smooth(r, z=None)``: ``degree`` recurrence steps from initial
    guess ``z`` (``None`` = zero).  The correction is a fixed polynomial in
    ``D^-1 L`` applied to ``D^-1 (r - L z)``, i.e. a symmetric operator —
    using the same polynomial pre and post keeps the V-cycle SPD.

    The polynomial itself (:func:`repro.kernels.vcycle_fused.cheby_recurrence`)
    is shared with the fused Pallas kernel, so the unfused composition and
    the fused kernel are the same computation by construction.
    """
    theta, delta, sigma = cheby_coeffs(rho)
    inv_d = (1.0 / diag)[:, None]

    def smooth(r, z=None):
        return cheby_recurrence(matvec, inv_d, r, z, degree=degree,
                                theta=theta, delta=delta, sigma=sigma)

    return smooth


def level_rhos(hier: Hierarchy) -> list:
    """Every level's ``rho(D^-1 L)`` estimate as host floats, always over
    the jnp reference matvec, so every ``matvec_impl`` bakes in the
    *identical* polynomial coefficients (the fused-vs-unfused
    iteration-count parity contract rests on this).

    The ONE designated build-time sync: the estimates are queued on the
    device and land in a single ``device_get`` instead of one blocking
    round-trip per level."""
    rho_dev = [estimate_dinv_rho_device(
        make_matvec(lev.idx, lev.val, "ref"), lev.diag)
        for lev in hier.levels]
    return [float(r) for r in jax.device_get(rho_dev)]


def make_vcycle(hier: Hierarchy, *, degree: int = 2,
                matvec_impl: str = "ref", tile_n: int = 256,
                interpret: Optional[bool] = None,
                rhos: Optional[list] = None) -> Callable:
    """Symmetric V(1,1)-cycle apply ``r [n, k] -> z ~= L_P^+ r``.

    Forward sweep (fine -> coarse): Chebyshev pre-smooth from zero,
    restrict the residual through the aggregation tree (segment-sum).
    Coarsest: dense triangular solves against the grounded Cholesky factor.
    Backward sweep (coarse -> fine): prolong (gather), Chebyshev
    post-smooth.  The level structure is static, so the recursion unrolls
    under jit.  ``degree`` is the Chebyshev polynomial degree (2 or 3 are
    the sweet spot); each level's spectral radius bound is ``rhos`` or,
    when omitted, :func:`level_rhos` at build time (a host sync, so a
    caller building the cycle inside ``jit`` passes ``rhos``).

    ``matvec_impl="fused"`` swaps each level's smoother for the fused
    Pallas Chebyshev kernel (one read of the idx/val slabs per sweep
    instead of per matvec) and the down-sweep residual + restriction for
    the fused restrict+residual kernel — the V-cycle's HBM traffic drops
    from ``(2*degree + 1)`` slab streams per level to 3.
    """
    fused = matvec_impl == "fused"
    if rhos is None:
        rhos = level_rhos(hier)
    if fused:
        matvecs = [make_matvec(lev.idx, lev.val, "fused", tile_n,
                               interpret=interpret) for lev in hier.levels]
        smoothers = [
            make_fused_chebyshev(lev.idx, lev.val, lev.diag, rho,
                                 degree=degree, interpret=interpret)
            for lev, rho in zip(hier.levels, rhos)]
        restricts = [
            make_fused_restrict_residual(lev.idx, lev.val, lev.agg,
                                         lev.n_coarse, interpret=interpret)
            for lev in hier.levels]
    else:
        matvecs = [make_matvec(lev.idx, lev.val, matvec_impl, tile_n,
                               interpret=interpret) for lev in hier.levels]
        smoothers = [
            make_chebyshev_smoother(mv, lev.diag, rho, degree=degree)
            for mv, lev, rho in zip(matvecs, hier.levels, rhos)]

    def coarse_solve(r):
        with named_scope("vcycle.coarse"):
            if hier.coarse_chol is None:  # single-vertex coarse graph
                return jnp.zeros_like(r)
            y = jax.scipy.linalg.cho_solve((hier.coarse_chol, True), r[1:])
            z = jnp.concatenate([jnp.zeros_like(r[:1]), y], axis=0)
            return _center(z)

    # named_scope labels are attached at trace time (zero runtime cost):
    # device timelines and HLO dumps show vcycle.L<l>.down/up per level
    # instead of one anonymous fusion soup.
    def cycle(l: int, r):
        if l == len(hier.levels):
            return coarse_solve(r)
        lev = hier.levels[l]
        mv, smooth = matvecs[l], smoothers[l]
        with named_scope(f"vcycle.L{l}.down"):
            z = smooth(r)                                   # pre-smooth
            if fused:                                       # restrict
                rc = restricts[l](r, z)
            else:
                rc = jax.ops.segment_sum(r - mv(z), lev.agg,
                                         num_segments=lev.n_coarse)
        zc = cycle(l + 1, rc)                               # coarse correct
        with named_scope(f"vcycle.L{l}.up"):
            z = z + zc[lev.agg]                             # prolong
            return smooth(r, z)                             # post-smooth

    def msolve(r):
        return _center(cycle(0, r))

    return msolve


def make_jacobi(diag) -> Callable:
    """Diagonal preconditioner (cheap middle ground for comparisons)."""
    d = diag[:, None]

    def msolve(r):
        return _center(r / d)

    return msolve


def _pcg_loop(matvec: Callable, b, msolve: Callable, tol, maxiter,
              colsum: Callable, center: Callable) -> BatchedPCGResult:
    """The one batched-PCG ``lax.while_loop``, parameterized over its
    reductions so the single-device and mesh-sharded planes share it.

    ``colsum(v) -> [k]`` sums a ``[rows, k]`` array over rows (plain
    ``jnp.sum`` on one device; local partial sum + ``psum`` under
    ``shard_map``) and ``center`` projects out the Laplacian nullspace.
    Everything else — per-column alpha/beta with converged columns frozen,
    the ``tol_inner = 0.5 * tol`` target — is identical by construction,
    which is what the sharded plane's iteration-count parity contract
    (counts within ±2 of the single-device solver) rests on.
    """
    k = b.shape[1]
    bnorm = jnp.sqrt(colsum(b * b))
    bn = jnp.maximum(bnorm, jnp.finfo(b.dtype).tiny)
    maxiter = jnp.broadcast_to(jnp.asarray(maxiter, jnp.int32), (k,))
    # The loop tracks the *recurrence* residual, which drifts away from the
    # true residual in f32; aiming below tol keeps the true relres
    # (recomputed at the end) near the caller's target.  The residual is
    # never replaced by ``b - A x`` inside the loop: in f32 that product
    # carries an error of about eps * ||A|| ||x||, which on graphs past ~15k
    # vertices exceeds tol * ||b||, and swapping it in without resetting the
    # search direction stalls CG there.  The service's f64 refinement closes
    # the gap between the recurrence and the true residual instead.
    tol_inner = 0.5 * tol

    x0 = jnp.zeros_like(b)
    z0 = msolve(b)
    rz0 = colsum(b * z0)
    done0 = (bnorm <= 0) | (maxiter <= 0)
    iters0 = jnp.zeros((k,), jnp.int32)
    state = (x0, b, z0, rz0, iters0, done0, jnp.int32(0))

    def cond(s):
        _, _, _, _, _, done, it = s
        return jnp.any(~done) & (it < jnp.max(maxiter))

    def body(s):
        x, r, p, rz, iters, done, it = s
        active = ~done
        Ap = matvec(p)
        pAp = colsum(p * Ap)
        alpha = jnp.where(active, rz / jnp.where(pAp != 0, pAp, 1.0), 0.0)
        x = x + alpha * p
        r = r - alpha * Ap
        relres = jnp.sqrt(colsum(r * r)) / bn
        iters = iters + active.astype(jnp.int32)
        done = done | (relres <= tol_inner) | (iters >= maxiter)
        z = msolve(r)
        rz_new = colsum(r * z)
        beta = rz_new / jnp.where(rz != 0, rz, 1.0)
        p = jnp.where(active, z + beta * p, p)
        rz = jnp.where(active, rz_new, rz)
        return x, r, p, rz, iters, done, it + 1

    x, _, _, _, iters, _, _ = jax.lax.while_loop(cond, body, state)
    x = center(x)
    relres = jnp.sqrt(colsum((b - matvec(x)) ** 2)) / bn  # true residual
    return BatchedPCGResult(x=x, iters=iters, relres=relres,
                            converged=relres <= tol)


def batched_pcg(matvec: Callable, b, msolve: Optional[Callable] = None,
                tol=1e-5, maxiter=2000) -> BatchedPCGResult:
    """PCG over a ``[n, k]`` RHS batch in one ``lax.while_loop``.

    Per-column step sizes; a converged column freezes (alpha forced to 0)
    while the rest keep iterating, so the loop runs until every column meets
    ``||b - Lx|| <= tol * ||b||`` or its iteration cap.  ``maxiter`` may be
    a scalar or a ``[k]`` array (per-column budgets for batched requests
    with different contracts).  Columns of ``b`` must be mean-zero (in
    ``range(L)``); use :func:`make_solver` for the end-to-end wrapper that
    centers and reports true residuals.
    """
    if msolve is None:
        msolve = lambda r: r  # noqa: E731
    return _pcg_loop(matvec, b, msolve, tol, maxiter,
                     colsum=lambda v: jnp.sum(v, axis=0), center=_center)


def make_solver(idx, val, hierarchy: Optional[Hierarchy] = None,
                precond: str = "hierarchy", matvec_impl: Optional[str] = None,
                tile_n: int = 256, mesh=None,
                shard_axis: str = "data",
                interpret: Optional[bool] = None) -> Callable:
    """Build the jit'd end-to-end solve ``(b [n, k], tol, maxiter) -> result``.

    ``precond``: "hierarchy" (V-cycle over ``hierarchy``), "jacobi", or
    "none".  Callers (the service) cache the returned function per graph
    so repeated solves pay zero setup.  The graph's arrays (ELL slabs and
    every hierarchy level) enter the jitted program as arguments, never as
    closed-over constants: a closed-over array is embedded in the program
    as a literal, which at 1e6 vertices puts hundreds of MB into every
    compile and every compilation-cache entry.

    ``matvec_impl``: "fused" (batched-RHS Pallas spmv + fused Chebyshev /
    restrict+residual kernels), "kernel" (per-column Pallas spmv), "ref"
    (jnp composition), or ``None`` for :func:`default_matvec_impl`.
    ``interpret`` forces Pallas interpret (``True``) or compiled Mosaic
    (``False``) mode; ``None`` resolves from the backend (see
    :func:`repro.kernels.ops.resolve_interpret`).

    ``mesh`` switches to the mesh-sharded plane: the ELL slabs (top level
    and every hierarchy level) are row-sharded over ``shard_axis`` and the
    whole PCG + V-cycle runs under ``shard_map`` — see
    :mod:`repro.solver.sharded`.  ``matvec_impl="fused"`` there contracts
    each shard's slab with the batched Pallas kernel.  The returned
    closure keeps this exact signature and global-array contract either
    way.
    """
    if mesh is not None:
        if matvec_impl == "kernel":
            import warnings
            warnings.warn(
                "matvec_impl='kernel' is ignored on the sharded path: each "
                "shard's ELL slab is contracted with the jnp reference "
                "matvec under shard_map (use matvec_impl='fused' for the "
                "batched per-shard Pallas contraction)", stacklevel=2)
            matvec_impl = "ref"
        # local import: sharded builds on this module's smoother/estimator
        from repro.solver.sharded import make_sharded_solver
        return make_sharded_solver(idx, val, hierarchy=hierarchy,
                                   precond=precond, mesh=mesh,
                                   shard_axis=shard_axis,
                                   matvec_impl=matvec_impl,
                                   tile_n=tile_n, interpret=interpret)
    if matvec_impl is None:
        matvec_impl = default_matvec_impl()
    if precond not in ("hierarchy", "jacobi", "none"):
        raise ValueError(f"unknown precond {precond!r}")
    rhos = None
    if precond == "hierarchy":
        if hierarchy is None:
            raise ValueError("precond='hierarchy' needs a Hierarchy")
        rhos = level_rhos(hierarchy)
    operands = (idx, val,
                hierarchy.arrays() if precond == "hierarchy" else None)

    @jax.jit
    def _solve(operands, b, tol, maxiter):
        idx, val, hier_arrays = operands
        matvec = make_matvec(idx, val, matvec_impl, tile_n,
                             interpret=interpret)
        if precond == "hierarchy":
            msolve = make_vcycle(hierarchy.with_arrays(hier_arrays),
                                 matvec_impl=matvec_impl, tile_n=tile_n,
                                 interpret=interpret, rhos=rhos)
        elif precond == "jacobi":
            n = idx.shape[0]
            diag = jnp.sum(val * (idx == jnp.arange(n)[:, None]), axis=1)
            msolve = make_jacobi(diag)
        else:
            msolve = None
        with named_scope("batched_pcg"):
            b = _center(b)
            return batched_pcg(matvec, b, msolve, tol=tol, maxiter=maxiter)

    def solve(b, tol=1e-5, maxiter=2000):
        return _solve(operands, b, tol, maxiter)

    solve._cache_size = _solve._cache_size   # read by the service's warmup
    return solve
