"""Pallas TPU kernel: ELLPACK SpMV for Laplacian matvecs (PCG inner loop).

The PCG application that consumes the sparsifier spends its time in
``y = L x``.  Ultra-sparse graph Laplacians (tree + alpha*|V| off-tree
edges) have bounded row degree after ELL padding, so we store the matrix
as dense [n, L] (column-index, value) slabs — the TPU-native layout:
contiguous, MXU/VPU-aligned, no CSR pointer chasing.

Tiling: rows stream through in ``tile_n`` slabs; the x vector stays fully
VMEM-resident (f32[n]; up to ~2M rows fits comfortably in 16 MB VMEM
alongside the slabs).  The per-slab gather ``x[idx]`` is a VMEM dynamic
gather, supported by Mosaic; the multiply-accumulate over the L (padded
degree) dimension is unrolled.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vcycle_fused import resolve_interpret


def _spmv_kernel(idx_ref, val_ref, x_ref, out_ref):
    idx = idx_ref[...]          # [Tn, L] int32
    val = val_ref[...]          # [Tn, L] f32
    x = x_ref[...]              # [n] f32 (resident)
    acc = jnp.zeros((idx.shape[0],), dtype=val.dtype)
    for l in range(idx.shape[1]):
        acc = acc + val[:, l] * x[idx[:, l]]
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def spmv_ell(idx, val, x, *, tile_n: int = 256,
             interpret: Optional[bool] = None):
    """y[i] = sum_l val[i, l] * x[idx[i, l]].  Rows padded with val = 0.

    Row counts that are not a multiple of ``tile_n`` are padded up to the
    tile boundary with zero-valued ELL entries (which gather ``x[0]`` and
    contribute nothing) and sliced back — arbitrary graph sizes never
    crash the kernel.  ``interpret=None`` resolves through
    :func:`repro.kernels.vcycle_fused.resolve_interpret`."""
    interpret = resolve_interpret(interpret)
    n, L = idx.shape
    pad = (-n) % tile_n
    if pad:
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
        val = jnp.pad(val, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _spmv_kernel,
        grid=((n + pad) // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, L), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, L), lambda i: (i, 0)),
            pl.BlockSpec(x.shape, lambda i: (0,)),   # x resident in VMEM
        ],
        out_specs=pl.BlockSpec((tile_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n + pad,), val.dtype),
        interpret=interpret,
    )(idx, val, x)
    return out[:n] if pad else out


def to_ell(graph, dtype=jnp.float32):
    """Host-side: Laplacian of a Graph/edge mask in ELL [n, L] layout.

    Vectorized scatter (no per-vertex python loop) — this runs once per
    hierarchy level at solver-setup time, so it must scale to 1e5+ rows.
    Layout per row v: the -w neighbor entries, then the diagonal (weighted
    degree), then padding slots that gather the row's own x with val = 0.
    """
    import numpy as np

    n = graph.n
    deg = np.diff(graph.indptr).astype(np.int64)
    L = int(deg.max()) + 1 if n else 1  # +1 for the diagonal
    rows = np.repeat(np.arange(n), deg)
    slot = np.arange(deg.sum()) - np.repeat(graph.indptr[:-1], deg)
    idx = np.broadcast_to(np.arange(n, dtype=np.int32)[:, None], (n, L)).copy()
    val = np.zeros((n, L), dtype=np.float64)
    idx[rows, slot] = graph.adj
    val[rows, slot] = -graph.adj_w.astype(np.float64)
    wdeg = np.zeros(n, dtype=np.float64)
    np.add.at(wdeg, rows, graph.adj_w.astype(np.float64))
    val[np.arange(n), deg] = wdeg
    return jnp.asarray(idx), jnp.asarray(val.astype(np.float32))
