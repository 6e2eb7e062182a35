"""The harness's one view of the solver's multilevel hierarchy.

How a level stores its sparsifier Laplacian is the program's decision
(today one ELL slab, ``idx``/``val`` of shape ``[n, L]``).  The benchmark
needs three facts about it, each through an accessor that the program may
define:

* ``SolverService.hierarchy(graph_or_handle) -> Hierarchy``: the graph's
  multilevel chain from the service's cached artifacts;
* ``Level.slab_shapes() -> ((rows, width), ...)``: every ELL block that
  the level's contraction reads (the byte model of the roofline);
* ``Level.laplacian_entries() -> (rows, cols, vals)``: numpy arrays of
  every stored nonzero of the level's sparsifier Laplacian, off-diagonal
  ``-w`` and the diagonal, in the level's vertex numbering, padding left
  out (the sparsifier check).

Where the program defines an accessor, its answer is taken as it stands,
so a change of the slab layout that keeps these accessors true needs no
edit of the benchmark.  Where it does not, the answer is read from the
one-slab layout: ``artifacts(graph) == (key, (idx, val, hierarchy),
source)``, one ``[n, L]`` slab per level, zero-valued padding.  No other
file of the harness reads the layout (``tests/bench`` checks it).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def hierarchy(svc, graph):
    """The multilevel chain that ``svc`` holds for ``graph``."""
    own = getattr(svc, "hierarchy", None)
    if own is not None:
        return own(graph)
    _, (_, _, hier), _ = svc.artifacts(graph)
    return hier


def slab_shapes(level) -> Tuple[Tuple[int, int], ...]:
    """``((rows, width), ...)`` of every ELL block of ``level``."""
    own = getattr(level, "slab_shapes", None)
    if own is not None:
        return tuple((int(r), int(w)) for r, w in own())
    return ((int(level.n), int(level.idx.shape[1])),)


def laplacian_entries(level):
    """``(rows, cols, vals)`` of every stored nonzero of ``level``'s
    sparsifier Laplacian, as host arrays."""
    own = getattr(level, "laplacian_entries", None)
    if own is not None:
        return tuple(np.asarray(a) for a in own())
    idx = np.asarray(level.idx)
    val = np.asarray(level.val)
    rows = np.broadcast_to(np.arange(idx.shape[0])[:, None], idx.shape)
    stored = val != 0
    return rows[stored], idx[stored], val[stored]
