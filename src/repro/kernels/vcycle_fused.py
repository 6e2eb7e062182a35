"""Pallas fused V-cycle kernel suite: batched ELL spmv, fused Chebyshev
smoother, fused restrict+residual.

The solve plane is memory-bound: a V-cycle application is a chain of ELL
matvecs, diagonal scalings and axpy combines, and the unfused composition
re-reads the ``[n, L]`` idx/val slabs from HBM for *every* matvec — the
degree-``d`` Chebyshev smoother alone streams them ``d`` times per sweep.
These kernels collapse the chain so each slab crosses HBM once per
logical pass:

  ``spmv_ell_batched``
      ``y[n, k] = A @ x[n, k]`` with the whole ``[n, k]`` RHS block VMEM
      resident — one kernel for a multi-column solve instead of ``k``
      single-column dispatches.
  ``make_fused_chebyshev``
      the entire degree-2/3 Chebyshev polynomial in ``D^-1 L`` (two/three
      matvecs + diagonal scaling + recurrence combines) as ONE
      ``pallas_call``: idx/val/diag/r (and the optional initial iterate)
      are DMA'd HBM->VMEM once, every matvec inside is a VMEM gather.
  ``make_fused_restrict_residual``
      ``rc = restrict(r - L z)`` — the residual matvec and the
      aggregation-tree segment-sum restriction in a single pass over the
      slabs, writing the ``[n_coarse, k]`` coarse residual directly.

Layout contract: the fused smoother / restrict kernels hold the full
level slabs and vectors VMEM-resident (no row tiling) — the recurrence
steps are globally data-dependent, so row tiles cannot stream without
cross-tile synchronization.  A level with ``n * L * 8 + ~3 n k * 4``
bytes over the ~16 MB VMEM budget should use the unfused path; every
hierarchy level this repo builds (ultra-sparse sparsifiers, bounded ELL
width) fits with room to spare.  ``spmv_ell_batched`` row-tiles like the
single-column kernel, with only ``x`` resident.

Numerics contract: kernel bodies are written op-for-op identical to the
unfused jnp composition (the same ``einsum`` contraction, the same
:func:`cheby_recurrence`, the same ``segment_sum``), so under
``interpret=True`` the fused V-cycle is *bit-identical* to the unfused
one and PCG iteration counts match exactly (asserted in
``tests/test_fused_vcycle.py``).

``interpret=None`` everywhere means "resolve automatically" — see
:func:`resolve_interpret`.

Mosaic refuses all three kernels today (the in-kernel ``x[idx]`` gather
has no TPU lowering; ``tests/test_tpu_compile.py`` records each refusal),
so the solve plane's declared default is the jnp reference path and these
kernels run only where a caller selects ``matvec_impl="fused"``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve the Pallas ``interpret`` knob.

    An explicit ``True``/``False`` wins.  ``None`` compiles through Mosaic
    on a TPU and interprets on every other backend, so a kernel runs in
    interpret mode on a TPU only when its caller asks for it by name.
    """
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def cheby_coeffs(rho: float):
    """Chebyshev smoother coefficients for eigenvalues of ``D^-1 L`` in
    ``[lmax/4, lmax]`` with ``lmax = 1.1 * rho`` (overestimating the
    spectral radius is benign; underestimating can amplify the top mode).
    Returns ``(theta, delta, sigma)`` — the interval midpoint, half-width,
    and their ratio."""
    lmax = 1.1 * rho
    lmin = lmax / 4.0
    theta = 0.5 * (lmax + lmin)
    delta = 0.5 * (lmax - lmin)
    return theta, delta, theta / delta


def cheby_recurrence(matvec: Callable, inv_d, r, z, *, degree: int,
                     theta: float, delta: float, sigma: float):
    """The degree-``degree`` Chebyshev recurrence for ``L z ~= r`` with
    Jacobi scaling — the ONE definition of the polynomial, shared by the
    unfused smoother closure (``device_pcg.make_chebyshev_smoother``) and
    the fused Pallas kernel body, so the two paths are identical by
    construction.  ``z=None`` starts from the zero iterate."""
    res = r if z is None else r - matvec(z)
    p = inv_d * res / theta
    z = p if z is None else z + p
    rho_prev = 1.0 / sigma
    for _ in range(degree - 1):
        res = r - matvec(z)
        rho_k = 1.0 / (2.0 * sigma - rho_prev)
        p = (rho_k * rho_prev) * p + (2.0 * rho_k / delta) * (inv_d * res)
        z = z + p
        rho_prev = rho_k
    return z


def ell_contract(idx, val, x):
    """``y[i, j] = sum_l val[i, l] * x[idx[i, l], j]`` for ``x [nx, k]``.

    The one definition of the ELL contraction: the jnp reference matvec,
    the sharded plane's per-shard matvec and the fused kernels' bodies all
    call it.  The gather runs on ``x.T`` so the row axis stays minor: the
    ``[n, L, k]`` intermediate of a plain ``x[idx]`` has small trailing
    axes that a TPU pads to its 128 lanes, which at n = 2^20, L = 8, k = 8
    is 4.5 GiB of temporaries against 256 MiB this way (the TPU compiler's
    memory analysis for a v5e)."""
    return jnp.einsum("nl,kln->nk", val, x.T[:, idx.T])


def _ell_matvec(idx, val):
    """In-kernel ELL contraction ``x [nx, k] -> [n, k]`` over VMEM-resident
    slabs — :func:`ell_contract`, as in the jnp reference path."""
    def mv(x):
        return ell_contract(idx, val, x)

    return mv


# ---------------------------------------------------------------------------
# Batched-RHS ELL spmv
# ---------------------------------------------------------------------------

def _spmv_batched_kernel(idx_ref, val_ref, x_ref, out_ref):
    out_ref[...] = _ell_matvec(idx_ref[...], val_ref[...])(x_ref[...])


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def spmv_ell_batched(idx, val, x, *, tile_n: int = 256,
                     interpret: Optional[bool] = None):
    """``y[i, j] = sum_l val[i, l] * x[idx[i, l], j]`` for a ``[nx, k]``
    RHS block in one kernel.

    Rows stream through in ``tile_n`` slabs; the whole ``x`` block stays
    VMEM resident.  ``x`` may have more rows than ``idx`` (the sharded
    plane gathers from ``[n_loc + halo]`` extended vectors).  Rows are
    padded up to the tile multiple with zero-valued ELL entries, so any
    ``n`` is accepted."""
    interpret = resolve_interpret(interpret)
    n, L = idx.shape
    pad = (-n) % tile_n
    if pad:
        # padding rows gather x[0] with val 0 — inert, sliced away below
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
        val = jnp.pad(val, ((0, pad), (0, 0)))
    nx, k = x.shape
    out = pl.pallas_call(
        _spmv_batched_kernel,
        grid=((n + pad) // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, L), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, L), lambda i: (i, 0)),
            pl.BlockSpec((nx, k), lambda i: (0, 0)),   # x resident in VMEM
        ],
        out_specs=pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, k), val.dtype),
        interpret=interpret,
    )(idx, val, x)
    return out[:n] if pad else out


# ---------------------------------------------------------------------------
# Fused Chebyshev smoother
# ---------------------------------------------------------------------------

def make_fused_chebyshev(idx, val, diag, rho: float, *, degree: int = 3,
                         interpret: Optional[bool] = None) -> Callable:
    """Build ``smooth(r, z=None)`` whose whole degree-``degree`` polynomial
    is one ``pallas_call``: the idx/val slabs and the diagonal are read
    from HBM once per sweep instead of once per matvec.  Coefficients are
    baked in at build time from the (host-estimated) spectral radius
    ``rho``, exactly as the unfused closure does."""
    theta, delta, sigma = cheby_coeffs(rho)
    interpret = resolve_interpret(interpret)

    def _kernel(idx_ref, val_ref, diag_ref, r_ref, *rest):
        z_ref = rest[0] if len(rest) == 2 else None
        out_ref = rest[-1]
        mv = _ell_matvec(idx_ref[...], val_ref[...])
        inv_d = (1.0 / diag_ref[...])[:, None]
        z = None if z_ref is None else z_ref[...]
        out_ref[...] = cheby_recurrence(mv, inv_d, r_ref[...], z,
                                        degree=degree, theta=theta,
                                        delta=delta, sigma=sigma)

    def smooth(r, z=None):
        args = (idx, val, diag, r) + (() if z is None else (z,))
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct(r.shape, r.dtype),
            interpret=interpret,
        )(*args)

    return smooth


# ---------------------------------------------------------------------------
# Fused restrict + residual
# ---------------------------------------------------------------------------

def make_fused_restrict_residual(idx, val, agg, n_coarse: int, *,
                                 interpret: Optional[bool] = None
                                 ) -> Callable:
    """Build ``restrict(r, z) -> rc [n_coarse, k]`` computing
    ``segment_sum(r - L z, agg)`` in a single pass over the slabs: the
    residual matvec's output never round-trips through HBM before the
    aggregation-tree scatter consumes it."""
    interpret = resolve_interpret(interpret)

    def _kernel(idx_ref, val_ref, agg_ref, r_ref, z_ref, out_ref):
        mv = _ell_matvec(idx_ref[...], val_ref[...])
        resid = r_ref[...] - mv(z_ref[...])
        out_ref[...] = jax.ops.segment_sum(resid, agg_ref[...],
                                           num_segments=n_coarse)

    def restrict(r, z):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct((n_coarse, r.shape[1]), r.dtype),
            interpret=interpret,
        )(idx, val, agg, r, z)

    return restrict
