"""jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default everywhere) resolves automatically via
:func:`resolve_interpret`: an explicit bool wins, else the kernels compile
through Mosaic when ``jax.default_backend()`` is TPU and interpret
everywhere else (CPU containers, CI).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.similarity import similarity_mark as _similarity_mark
from repro.kernels.spmv_ell import spmv_ell as _spmv_ell, to_ell  # noqa: F401
from repro.kernels.vcycle_fused import (  # noqa: F401
    make_fused_chebyshev, make_fused_restrict_residual, resolve_interpret,
    spmv_ell_batched as _spmv_ell_batched)


def similarity_mark(csu, csv, cbeta, cseg, esu, esv, eseg,
                    tile_m: int = 512, interpret: bool | None = None):
    m = esu.shape[0]
    if m % tile_m != 0:  # pad to tile multiple with inert rows
        pad = tile_m - m % tile_m
        esu = jnp.pad(esu, ((0, pad), (0, 0)), constant_values=-1)
        esv = jnp.pad(esv, ((0, pad), (0, 0)), constant_values=-1)
        eseg = jnp.pad(eseg, (0, pad), constant_values=-1)
    out = _similarity_mark(csu, csv, cbeta, cseg, esu, esv, eseg,
                           tile_m=tile_m, interpret=interpret)
    return out[:m]


def spmv(idx, val, x, tile_n: int = 256, interpret: bool | None = None):
    """Single-column ELL spmv; non-tile-multiple row counts pad inside
    the kernel wrapper."""
    return _spmv_ell(idx, val, x, tile_n=tile_n, interpret=interpret)


def spmv_batched(idx, val, x, tile_n: int = 256,
                 interpret: bool | None = None):
    """Batched-RHS ELL spmv: the whole ``[n, k]`` block in one kernel."""
    return _spmv_ell_batched(idx, val, x, tile_n=tile_n, interpret=interpret)


similarity_mark_ref = _ref.similarity_mark_ref
spmv_ref = _ref.spmv_ell_ref
