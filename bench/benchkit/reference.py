"""The plain reference: what a correct answer is, in float64.

It imports nothing of the program and takes nothing the program made: the
Laplacian is assembled by scipy from the benchmark's own edge arrays.

* A solve is correct when its true relative residual,
  ``||b_c - L x|| / ||b_c||`` with ``b_c`` the mean-free part of ``b``
  (the part a Laplacian can reach), is at most the request's ``tol``,
  computed here in float64.
* A sparsifier (pdGRASS's output: a spanning tree plus recovered off-tree
  edges) is correct when every edge of it is an edge of the graph with the
  graph's own weight, it connects every vertex, and it keeps at most
  ``n - 1 + ceil(alpha * n)`` edges.  It is read from the ELL slabs of its
  Laplacian (``idx``/``val`` rows: ``-w`` per neighbour, the weighted
  degree on the diagonal, zero-valued padding), the form the solve uses.
"""
from __future__ import annotations

import math

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components


def laplacian(n: int, src, dst, weight) -> sp.csr_matrix:
    """``L = D - A`` in float64 from undirected edge arrays."""
    w = np.asarray(weight, np.float64)
    a = sp.coo_matrix((w, (np.asarray(src), np.asarray(dst))),
                      shape=(n, n)).tocsr()
    a = a + a.T
    return (sp.diags(np.asarray(a.sum(axis=1)).ravel()) - a).tocsr()


def relres(lap: sp.csr_matrix, b, x) -> np.ndarray:
    """Per-column float64 relative residual over the mean-free part of b."""
    n = lap.shape[0]
    b = np.asarray(b, np.float64).reshape(n, -1)
    b = b - b.mean(axis=0)
    x = np.asarray(x, np.float64).reshape(n, -1)
    if x.shape != b.shape:
        return np.full(b.shape[1], np.inf)
    return np.linalg.norm(b - lap @ x, axis=0) / np.linalg.norm(b, axis=0)


def sparsifier_edges(idx, val):
    """``(src, dst, w)`` with ``src < dst`` of the off-diagonal entries of
    an ELL Laplacian slab, and whether the slab is symmetric."""
    idx = np.asarray(idx)
    val = np.asarray(val)
    rows = np.broadcast_to(np.arange(idx.shape[0])[:, None], idx.shape)
    off = (idx != rows) & (val != 0)
    r, c, v = rows[off], idx[off], -val[off]
    upper = r < c
    fwd = {(int(a), int(b)): float(x)
           for a, b, x in zip(r[upper], c[upper], v[upper])}
    bwd = {(int(b), int(a)): float(x)
           for a, b, x in zip(r[~upper], c[~upper], v[~upper])}
    symmetric = fwd == bwd
    keys = np.asarray(sorted(fwd), np.int64).reshape(-1, 2)
    w = np.asarray([fwd[tuple(k)] for k in keys.tolist()], np.float64)
    return keys[:, 0], keys[:, 1], w, symmetric


def sparsifier_faults(n: int, src, dst, weight, idx, val,
                      alpha: float) -> dict:
    """Counts of each way the sparsifier in ``idx``/``val`` breaks the
    guarantees; all zero when it is correct."""
    s, d, w, symmetric = sparsifier_edges(idx, val)
    graph_w = {(int(a), int(b)): float(x)
               for a, b, x in zip(src, dst, np.asarray(weight, np.float32))}
    not_in_graph = sum(
        1 for a, b, x in zip(s.tolist(), d.tolist(), w.tolist())
        if graph_w.get((a, b)) != float(np.float32(x)))
    adj = sp.coo_matrix((np.ones(len(s)), (s, d)), shape=(n, n))
    components, _ = connected_components(adj, directed=False)
    budget = n - 1 + math.ceil(alpha * n)
    return {
        "sparsifier_edges_not_in_graph": int(not_in_graph),
        "sparsifier_asymmetric_rows": int(not symmetric),
        "sparsifier_extra_components": int(components - 1),
        "sparsifier_edges_over_budget": int(max(0, len(s) - budget)),
    }
