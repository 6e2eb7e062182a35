"""Peaks of each chip, and the bytes a kernel has to move.

The peaks table (``bench/peaks.json``) is keyed by JAX's ``device_kind``;
a kind that is not in it is an error, never a default.

Byte model of the solve plane's level-0 V-cycle, as the program's
unfused V-cycle runs it (degree-2 Chebyshev, ``vcycle.L0.down/up``):
the pre-smoother from zero makes 1 ELL contraction, the restricted
residual 1, the post-smoother from a guess 2: four contractions per
application.  A contraction reads every ELL block of the level (the
program's ``slab_shapes``, see ``layout.py``); over a ``[rows, L]`` block
with ``k`` columns it moves at least the block (``rows * L`` int32
indices and float32 values), one gathered float32 per slot and column,
and its ``[rows, k]`` output.
Restriction and prolongation read the ``[n]`` aggregate map and move one
``[n, k]`` block each way.  This is a floor: any schedule of the same
work moves at least this much, so the roofline share it gives cannot
exceed what the chip allows.
"""
from __future__ import annotations

import json
import os

from benchkit.spec import BENCH_DIR

F32 = 4
I32 = 4
CONTRACTIONS_PER_LEVEL = 4


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; have {sorted(table)}")
    return table[device_kind]


def ell_contract_bytes(n: int, width: int, k: int) -> int:
    """Least HBM traffic of ``y[n, k] = ELL(idx, val) @ x``."""
    return n * width * (I32 + F32) + n * width * k * F32 + n * k * F32


def vcycle_level_bytes(n: int, blocks, k: int) -> int:
    """Least HBM traffic of one V-cycle application at one fine level of
    ``n`` vertices whose contraction reads the ELL ``blocks``, ``((rows,
    width), ...)``."""
    transfer = 2 * (n * I32 + 2 * n * k * F32)   # restrict + prolong
    return CONTRACTIONS_PER_LEVEL * sum(
        ell_contract_bytes(rows, width, k) for rows, width in blocks) + \
        transfer
