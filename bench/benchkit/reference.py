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
  ``n - 1 + ceil(alpha * n)`` edges.  It is read from the stored entries
  of its Laplacian as ``(rows, cols, vals)`` triplets: ``-w`` per
  neighbour, the weighted degree on the diagonal, in any order and split
  however the program stores them; entries of one position add up, as in
  any sparse matrix.
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


def sparsifier_edges(rows, cols, vals):
    """``(src, dst, w)`` with ``src < dst`` of the off-diagonal entries of
    a Laplacian given as ``(rows, cols, vals)`` triplets, and whether it is
    symmetric."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals, np.float64)
    off = rows != cols
    size = int(max(rows.max(initial=-1), cols.max(initial=-1))) + 1
    a = sp.csr_matrix((-vals[off], (rows[off], cols[off])),
                      shape=(size, size))
    a.eliminate_zeros()
    symmetric = (a != a.T).nnz == 0
    upper = sp.triu(a, k=1).tocoo()
    return (upper.row.astype(np.int64), upper.col.astype(np.int64),
            upper.data, symmetric)


def sparsifier_faults(n: int, src, dst, weight, rows, cols, vals,
                      alpha: float) -> dict:
    """Counts of each way the sparsifier whose Laplacian has the entries
    ``(rows, cols, vals)`` breaks the guarantees; all zero when it is
    correct."""
    s, d, w, symmetric = sparsifier_edges(rows, cols, vals)
    key = np.asarray(src, np.int64) * n + np.asarray(dst, np.int64)
    order = np.argsort(key)
    key, graph_w = key[order], np.asarray(weight, np.float32)[order]
    at = np.minimum(np.searchsorted(key, s * n + d), len(key) - 1)
    in_graph = ((d < n) & (key[at] == s * n + d)
                & (graph_w[at] == w.astype(np.float32)))
    size = max(n, int(d.max(initial=-1)) + 1)
    adj = sp.coo_matrix((np.ones(len(s)), (s, d)), shape=(size, size))
    components, _ = connected_components(adj, directed=False)
    budget = n - 1 + math.ceil(alpha * n)
    return {
        "sparsifier_edges_not_in_graph": int((~in_graph).sum()),
        "sparsifier_asymmetric_rows": int(not symmetric),
        "sparsifier_extra_components": int(components - 1),
        "sparsifier_edges_over_budget": int(max(0, len(s) - budget)),
    }
