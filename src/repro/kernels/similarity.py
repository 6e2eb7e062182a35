"""Pallas TPU kernel: strict-similarity marking pass (pdGRASS step 4 hot spot).

The quadratic term in the paper's work bound is the pairwise similarity
check inside each subtask.  With the ancestor-signature reduction (see
``repro.core.lifting``), checking whether recovered edge k marks edge j is

    sim(k, j) = (u_j in S_{u_k, beta_k}  and  v_j in S_{v_k, beta_k})
             or (u_j in S_{v_k, beta_k}  and  v_j in S_{u_k, beta_k})

where membership is ``exists a+b <= beta_k: sig_x[k, a] == sig_y[j, b]`` —
a fixed (c+1)^2 grid of int32 equality tests.  No gathers, no BFS: the
whole pass is data-independent dense VPU work, which is exactly what the
MXU-adjacent vector units want.

Tiling: the K candidate rows (K <= 128, with their 9-entry signatures)
stay resident in VMEM across the whole grid; edges stream through in
``tile_m``-row slabs.  The (a, b) loop is unrolled at trace time and pairs
with a+b > c are statically skipped (45 of 81 survive for c = 8).

Block layout per grid step (c1 = 9, int32):
    candidates:  4 x [K, c1]   ~ 18 KB   (replicated across grid)
    edge slab:   2 x [tile_m, c1] + [tile_m]   ~ 9.4 KB per 128 rows
    accumulators: 4 x [K, tile_m] bool
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vcycle_fused import resolve_interpret


def _sim_kernel(csu_ref, csv_ref, cbeta_ref, cseg_ref,
                esu_ref, esv_ref, eseg_ref, out_ref, *, c1: int):
    csu = csu_ref[...]          # [K, c1]
    csv = csv_ref[...]
    cbeta = cbeta_ref[...]      # [K]
    cseg = cseg_ref[...]        # [K]
    esu = esu_ref[...]          # [Tm, c1]
    esv = esv_ref[...]
    eseg = eseg_ref[...]        # [Tm]

    K = csu.shape[0]
    Tm = esu.shape[0]
    cmax = c1 - 1

    acc_uu = jnp.zeros((K, Tm), dtype=jnp.bool_)
    acc_vv = jnp.zeros((K, Tm), dtype=jnp.bool_)
    acc_uv = jnp.zeros((K, Tm), dtype=jnp.bool_)
    acc_vu = jnp.zeros((K, Tm), dtype=jnp.bool_)
    for a in range(c1):
        for b in range(c1):
            if a + b > cmax:
                continue  # static skip: beta <= c always
            ok = ((a + b) <= cbeta)[:, None]          # [K, 1]
            cu_a = csu[:, a][:, None]                 # [K, 1]
            cv_a = csv[:, a][:, None]
            eu_b = esu[:, b][None, :]                 # [1, Tm]
            ev_b = esv[:, b][None, :]
            acc_uu |= ok & (cu_a == eu_b)
            acc_vv |= ok & (cv_a == ev_b)
            acc_uv |= ok & (cu_a == ev_b)
            acc_vu |= ok & (cv_a == eu_b)
    sim = (acc_uu & acc_vv) | (acc_uv & acc_vu)
    sim &= cseg[:, None] == eseg[None, :]
    out_ref[...] = jnp.any(sim, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("tile_m", "interpret"))
def similarity_mark(csu, csv, cbeta, cseg, esu, esv, eseg,
                    *, tile_m: int = 512, interpret: Optional[bool] = None):
    """kill[j] = any recovered candidate k (same subtask) marks edge j.

    Args:
      csu/csv:   [K, c1] int32 candidate signatures (beta < 0 disables row).
      cbeta:     [K] int32.
      cseg:      [K] int32 subtask ids (use -2 for invalid rows).
      esu/esv:   [m, c1] int32 edge slab signatures; m % tile_m == 0.
      eseg:      [m] int32 (-1 for padding rows).
    Returns: [m] bool.

    ``interpret=None`` resolves through
    :func:`repro.kernels.vcycle_fused.resolve_interpret`.
    """
    interpret = resolve_interpret(interpret)
    m, c1 = esu.shape
    assert m % tile_m == 0, (m, tile_m)
    grid = (m // tile_m,)
    kern = functools.partial(_sim_kernel, c1=c1)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(csu.shape, lambda i: (0, 0)),   # candidates resident
            pl.BlockSpec(csv.shape, lambda i: (0, 0)),
            pl.BlockSpec(cbeta.shape, lambda i: (0,)),
            pl.BlockSpec(cseg.shape, lambda i: (0,)),
            pl.BlockSpec((tile_m, c1), lambda i: (i, 0)),  # edge slabs stream
            pl.BlockSpec((tile_m, c1), lambda i: (i, 0)),
            pl.BlockSpec((tile_m,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((tile_m,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((m,), jnp.bool_),
        interpret=interpret,
    )(csu, csv, cbeta, cseg, esu, esv, eseg)
