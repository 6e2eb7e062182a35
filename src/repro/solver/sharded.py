"""Mesh-sharded solve plane: row-sharded batched PCG + Chebyshev V-cycle.

The single-device solve plane (:mod:`repro.solver.device_pcg`) caps the
"millions of users" target at one accelerator's HBM.  This module runs the
*same* algorithms under ``shard_map`` on the mesh that
:mod:`repro.core.distributed` already uses for recovery, so one mesh covers
sparsify + precondition + solve end to end:

  * **Row sharding.** Every level's ELL slabs — and every solve vector —
    are row-sharded over the mesh axis (``P(axis, None)``), padded so the
    axis size divides the row count.  Padding rows are self-loops of weight
    zero: a zero operator block that provably never leaks into the live
    rows (their matvec output is zero and nothing gathers from them).
  * **Halo matvec.** The ELL column indices are rewritten *per shard* into
    local coordinates at closure-build time: targets inside the shard's own
    row block index the local slab directly, remote targets index a
    precomputed per-shard **halo** list (the sorted unique remote rows that
    shard's slab actually references).  The exchange itself is one
    ``all_gather`` of the sharded ``x`` followed by a local halo gather —
    on a real mesh the halo bounds what each shard touches, and the
    transport can specialize to a neighborhood exchange without changing
    the slab layout.
  * **Collective reductions.** PCG dot products and norms are local partial
    sums + ``psum``; centering (the Laplacian nullspace projection) masks
    the padding rows and divides by the *true* row count.
  * **Sharded V-cycle.** Restriction is a local segment-sum into the full
    coarse vector + ``psum`` (then each shard keeps its own coarse block);
    prolongation is an ``all_gather`` + aggregation-tree gather; the tiny
    coarsest Cholesky solve is replicated on every shard.  Smoother
    coefficients (per-level Chebyshev spectral radius) are estimated on the
    *unsharded* slabs at build time, so the sharded cycle applies the
    identical polynomial — which is what keeps per-column iteration counts
    within noise of the single-device solver.

:func:`make_sharded_solver` returns a closure with the exact signature of
:func:`repro.solver.device_pcg.make_solver`'s product — ``solve(b [n, k],
tol, maxiter) -> BatchedPCGResult`` on *global* arrays — so the service
swaps it in purely by passing ``SolverService(mesh=...)``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels import ops
from repro.kernels.vcycle_fused import ell_contract
from repro.obs import get_tracer
from repro.obs.device import named_scope
from repro.solver.device_pcg import (BatchedPCGResult, _pcg_loop,
                                     estimate_dinv_rho_device,
                                     make_chebyshev_smoother, make_matvec)
from repro.solver.hierarchy import Hierarchy


class ShardedSlab(NamedTuple):
    """Row-sharded ELL slabs with per-shard local coordinates.

    ``idx`` entries are *local*: ``t < n_loc`` addresses the shard's own
    row ``t``; ``t >= n_loc`` addresses slot ``t - n_loc`` of the shard's
    halo.  ``halo`` is flat ``[n_sh * H]`` (``P(axis)`` hands each shard
    its ``[H]`` slice of global row ids to gather after the all_gather).
    """

    idx: jnp.ndarray    # [n_pad, L] int32 local coords
    val: jnp.ndarray    # [n_pad, L] f32
    halo: jnp.ndarray   # [n_sh * H] int32 global row ids


class SlabMeta(NamedTuple):
    n: int        # true row count
    n_pad: int    # padded row count (divisible by n_sh)
    n_loc: int    # rows per shard
    halo: int     # halo slots per shard


class ShardedLevel(NamedTuple):
    """One sharded V-cycle level: slabs + smoother diagonal + aggregation."""

    slab: ShardedSlab
    diag: jnp.ndarray   # [n_pad] f32, 1.0 on padding rows
    agg: jnp.ndarray    # [n_pad] int32 coarse ids; nc_pad on padding rows


class LevelMeta(NamedTuple):
    slab: SlabMeta
    rho: float          # Chebyshev spectral-radius bound (unsharded estimate)
    nc: int             # true coarse row count
    nc_pad: int
    nc_loc: int


def shard_ell_slabs(idx, val, n_sh: int):
    """Host-side prep: global ELL slabs -> (:class:`ShardedSlab` arrays,
    :class:`SlabMeta`).

    Pads rows to a multiple of ``n_sh`` with weight-zero self-loops, then
    rewrites every shard's column indices into [own rows | halo] local
    coordinates.  The halo of shard ``s`` is the sorted unique set of
    global rows outside its block that its slab references — precomputed
    once here, gathered on every matvec.
    """
    idx = np.asarray(idx)
    val = np.asarray(val)
    n, L = idx.shape
    n_loc = -(-n // n_sh)
    n_pad = n_loc * n_sh
    idx_g = np.empty((n_pad, L), np.int32)
    val_p = np.zeros((n_pad, L), val.dtype)
    idx_g[:n] = idx
    val_p[:n] = val
    idx_g[n:] = np.arange(n, n_pad, dtype=np.int32)[:, None]

    halos = []
    for s in range(n_sh):
        r0 = s * n_loc
        blk = idx_g[r0:r0 + n_loc]
        own = (blk >= r0) & (blk < r0 + n_loc)
        halos.append(np.unique(blk[~own]))
    H = max(1, max(h.shape[0] for h in halos))
    halo = np.empty((n_sh, H), np.int32)
    idx_l = np.empty_like(idx_g)
    for s, h in enumerate(halos):
        r0 = s * n_loc
        halo[s, :h.shape[0]] = h
        halo[s, h.shape[0]:] = r0          # own row: never referenced
        blk = idx_g[r0:r0 + n_loc]
        own = (blk >= r0) & (blk < r0 + n_loc)
        idx_l[r0:r0 + n_loc] = np.where(
            own, blk - r0, n_loc + np.searchsorted(h, blk))
    slab = ShardedSlab(idx=jnp.asarray(idx_l), val=jnp.asarray(val_p),
                       halo=jnp.asarray(halo.reshape(-1)))
    return slab, SlabMeta(n=n, n_pad=n_pad, n_loc=n_loc, halo=H)


def _prep_level(lev, n_sh: int):
    """One hierarchy level -> (:class:`ShardedLevel`, :class:`LevelMeta`,
    device rho estimate).  The meta's ``rho`` is a placeholder: the caller
    batches every level's device estimate into one ``device_get`` and
    patches the metas, instead of blocking once per level here."""
    slab, meta = shard_ell_slabs(lev.idx, lev.val, n_sh)
    diag = np.ones((meta.n_pad,), np.float32)
    diag[:meta.n] = np.asarray(lev.diag, np.float32)
    nc_loc = -(-lev.n_coarse // n_sh)
    nc_pad = nc_loc * n_sh
    agg = np.full((meta.n_pad,), nc_pad, np.int32)   # pad rows: dropped
    agg[:meta.n] = np.asarray(lev.agg, np.int32)
    rho_dev = estimate_dinv_rho_device(
        make_matvec(lev.idx, lev.val, "ref"), lev.diag)
    return (ShardedLevel(slab=slab, diag=jnp.asarray(diag),
                         agg=jnp.asarray(agg)),
            LevelMeta(slab=meta, rho=0.0, nc=lev.n_coarse,
                      nc_pad=nc_pad, nc_loc=nc_loc),
            rho_dev)


def _local_matvec(slab_loc: ShardedSlab, axis: str, impl: str = "ref",
                  tile_n: int = 256, interpret=None):
    """Sharded ELL matvec ``[n_loc, k] -> [n_loc, k]`` for shard_map bodies:
    one all_gather of the sharded ``x``, a halo gather, a local contraction.

    ``impl="fused"`` contracts each shard's slab with the batched-RHS
    Pallas kernel (:func:`repro.kernels.ops.spmv_batched`) instead of the
    jnp einsum.  Fusion on the sharded plane stops at the per-shard
    contraction: the halo ``all_gather`` between successive matvecs is a
    collective, so the Chebyshev sweep cannot fuse across matvecs the way
    the single-device :func:`~repro.kernels.vcycle_fused.make_fused_chebyshev`
    kernel does — the smoother stays composed from fused local matvecs.
    """
    def mv(x_loc):
        xg = jax.lax.all_gather(x_loc, axis, tiled=True)     # [n_pad, k]
        x_ext = jnp.concatenate([x_loc, xg[slab_loc.halo]], axis=0)
        if impl == "fused":
            return ops.spmv_batched(slab_loc.idx, slab_loc.val, x_ext,
                                    tile_n=tile_n, interpret=interpret)
        return ell_contract(slab_loc.idx, slab_loc.val, x_ext)

    return mv


def make_sharded_solver(idx, val, hierarchy: Optional[Hierarchy] = None,
                        precond: str = "hierarchy", *, mesh,
                        shard_axis: str = "data",
                        degree: int = 2, matvec_impl: str = "ref",
                        tile_n: int = 256, interpret=None):
    """Build the jit'd mesh-sharded ``solve(b, tol, maxiter)`` closure.

    Same contract as :func:`repro.solver.device_pcg.make_solver`: global
    ``[n, k]`` right-hand sides in, :class:`BatchedPCGResult` out (mean-zero
    solutions, per-column iteration counts, true relative residuals).  The
    matvec is the local-slab contraction of :func:`_local_matvec`;
    ``matvec_impl="fused"`` swaps in the batched-RHS Pallas kernel for each
    shard's local contraction (see :func:`_local_matvec` for why sharded
    fusion stops at the per-shard matvec).  ``precond`` supports
    ``"hierarchy"`` and ``"none"``; ``"jacobi"`` is a single-device
    comparison baseline and is not sharded.
    """
    if matvec_impl not in ("ref", "fused"):
        raise ValueError(
            f"sharded matvec_impl must be 'ref' or 'fused', got "
            f"{matvec_impl!r}")
    if precond == "hierarchy" and hierarchy is None:
        raise ValueError("precond='hierarchy' needs a Hierarchy")
    if precond == "jacobi":
        raise NotImplementedError(
            "precond='jacobi' is a single-device comparison baseline — "
            "the sharded path supports 'hierarchy' and 'none'")
    if precond not in ("hierarchy", "none"):
        raise ValueError(f"unknown precond {precond!r}")
    axis = shard_axis
    n_sh = int(mesh.shape[axis])
    n = int(np.asarray(idx).shape[0])

    tracer = get_tracer()
    with tracer.span("sharded.shard_slabs", n=n, n_sh=n_sh):
        top_slab, top_meta = shard_ell_slabs(idx, val, n_sh)
    levels: tuple = ()
    level_meta: tuple = ()
    coarse_chol = None
    coarse_n = n
    if precond == "hierarchy":
        with tracer.span("sharded.prep_levels",
                         levels=len(hierarchy.levels), n_sh=n_sh):
            prepped = [_prep_level(lev, n_sh) for lev in hierarchy.levels]
        levels = tuple(p[0] for p in prepped)
        # the ONE designated build-time sync: all level rho estimates in a
        # single device_get (they queue and overlap on device)
        rhos = jax.device_get([p[2] for p in prepped])
        level_meta = tuple(p[1]._replace(rho=float(r))
                           for p, r in zip(prepped, rhos))
        coarse_chol = hierarchy.coarse_chol
        coarse_n = hierarchy.coarse_n
    ncs_loc = -(-coarse_n // n_sh)
    ncs_pad = ncs_loc * n_sh
    n_levels = len(levels)
    have_chol = coarse_chol is not None
    if not have_chol:
        coarse_chol = jnp.zeros((1, 1), jnp.float32)  # placeholder arg

    def _colsum(x_loc):
        return jax.lax.psum(jnp.sum(x_loc, axis=0), axis)

    def _pcenter(x_loc):
        """Mean-zero projection over the TRUE rows (padding masked out);
        the constant shift lands on padding rows too, harmlessly — they
        are sliced away on the way out."""
        my = jax.lax.axis_index(axis)
        rows = my * top_meta.n_loc + jnp.arange(top_meta.n_loc,
                                                dtype=jnp.int32)
        valid = (rows < n)[:, None]
        s = jax.lax.psum(
            jnp.sum(jnp.where(valid, x_loc, 0.0), axis=0), axis)
        return x_loc - s / n

    def _core(b_loc, tol, maxiter, top_loc, levels_loc, chol):
        k = b_loc.shape[1]
        matvec = _local_matvec(top_loc, axis, matvec_impl, tile_n, interpret)

        # -- preconditioner ------------------------------------------------
        lev_mvs = [_local_matvec(ll.slab, axis, matvec_impl, tile_n,
                                 interpret) for ll in levels_loc]
        smoothers = [make_chebyshev_smoother(mv, ll.diag, lm.rho,
                                             degree=degree)
                     for mv, ll, lm in zip(lev_mvs, levels_loc, level_meta)]

        def coarse_solve(r_loc):
            rg = jax.lax.all_gather(r_loc, axis, tiled=True)[:coarse_n]
            if not have_chol:                # single-vertex coarse graph
                return jnp.zeros_like(r_loc)
            y = jax.scipy.linalg.cho_solve((chol, True), rg[1:])
            z = jnp.concatenate([jnp.zeros_like(rg[:1]), y], axis=0)
            z = z - jnp.mean(z, axis=0, keepdims=True)
            zp = jnp.zeros((ncs_pad, k), r_loc.dtype).at[:coarse_n].set(z)
            my = jax.lax.axis_index(axis)
            return jax.lax.dynamic_slice_in_dim(zp, my * ncs_loc, ncs_loc)

        def cycle(l, r_loc):
            if l == n_levels:
                with named_scope("sharded_vcycle.coarse"):
                    return coarse_solve(r_loc)
            ll, lm = levels_loc[l], level_meta[l]
            mv, smooth = lev_mvs[l], smoothers[l]
            with named_scope(f"sharded_vcycle.L{l}.down"):
                z = smooth(r_loc)                             # pre-smooth
                resid = r_loc - mv(z)
                rc = jax.lax.psum(                            # restrict
                    jnp.zeros((lm.nc_pad, k), r_loc.dtype)
                    .at[ll.agg].add(resid, mode="drop"), axis)
                my = jax.lax.axis_index(axis)
                rc_loc = jax.lax.dynamic_slice_in_dim(
                    rc, my * lm.nc_loc, lm.nc_loc)
            zc = cycle(l + 1, rc_loc)                         # coarse correct
            with named_scope(f"sharded_vcycle.L{l}.up"):
                zc_full = jax.lax.all_gather(zc, axis, tiled=True)
                z = z + zc_full[jnp.minimum(ll.agg, lm.nc_pad - 1)]  # prolong
                return smooth(r_loc, z)                       # post-smooth

        if precond == "hierarchy":
            def msolve(r_loc):
                return _pcenter(cycle(0, r_loc))
        else:
            def msolve(r_loc):
                return r_loc

        # the SAME while_loop as the single-device plane — only the column
        # reduction (psum) and the centering (pad-masked) differ, so
        # per-column iteration counts agree up to f32 reduction-order noise
        res = _pcg_loop(matvec, b_loc, msolve, tol, maxiter,
                        colsum=_colsum, center=_pcenter)
        # the solution leaves replicated: slicing the padding rows off a
        # row-sharded [n_pad, k] is refused on a mesh with explicit axes
        # (jax.make_mesh's default) whenever n_sh does not divide n
        x = jax.lax.all_gather(res.x, axis, tiled=True)
        return x, res.iters, res.relres, res.converged

    slab_spec = ShardedSlab(idx=P(axis, None), val=P(axis, None),
                            halo=P(axis))
    level_spec = tuple(
        ShardedLevel(slab=slab_spec, diag=P(axis), agg=P(axis))
        for _ in range(n_levels))
    operand_spec = (slab_spec, level_spec, P())
    in_specs = (P(axis, None), P(), P()) + operand_spec
    out_specs = (P(), P(), P(), P())

    # check_vma=False: the replicated outputs are an all_gather and psum'd
    # reductions, correct by construction but not provable to the vma type
    # check (all_gather stays "varying"); the check also trips inside the
    # Pallas interpreter that the fused impl runs under on CPU.
    sharded = jax.shard_map(
        _core, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)

    # Place every slab on the mesh by its spec at build time.  The operands
    # enter the jitted program as arguments: closed over, they would be
    # embedded as literals and sit on one device until resharded.
    with tracer.span("sharded.place", n_sh=n_sh) as place_span:
        operands = jax.device_put(
            (top_slab, levels, coarse_chol),
            jax.tree.map(lambda s: NamedSharding(mesh, s), operand_spec))
        place_span.set(shardings=[
            f"{a.shape} {a.sharding.spec} on "
            f"{len(a.sharding.device_set)} devices"
            for a in jax.tree.leaves(operands)])

    n_pad = top_meta.n_pad

    @jax.jit
    def _solve(operands, b, tol, maxiter):
        b = b - jnp.mean(b, axis=0, keepdims=True)
        k = b.shape[1]
        bp = jnp.zeros((n_pad, k), b.dtype).at[:n].set(b)
        tol_a = jnp.broadcast_to(jnp.asarray(tol, b.dtype), (k,))
        mi_a = jnp.broadcast_to(jnp.asarray(maxiter, jnp.int32), (k,))
        x, iters, relres, conv = sharded(bp, tol_a, mi_a, *operands)
        return BatchedPCGResult(x=x[:n], iters=iters, relres=relres,
                                converged=conv)

    def solve(b, tol=1e-5, maxiter=2000):
        return _solve(operands, b, tol, maxiter)

    solve._cache_size = _solve._cache_size   # read by the service's warmup
    return solve
