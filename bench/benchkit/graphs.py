"""Graph generators of the benchmark's configurations.

A configuration names a family and its scale; the graph is made from the
configuration's own fixed ``graph_seed`` (never from a run's ``--seed``),
so every run of a cell serves the same graph, the way a deployment serves
one fixed mesh.  Weights are uniform in [1, 10], as the pdGRASS paper
states for its test graphs.

Each generator returns canonical edge arrays: ``src < dst``, no
duplicates, ``float32`` weights.  The reference assembles its Laplacian
from exactly these arrays.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import numpy as np

Edges = Tuple[int, np.ndarray, np.ndarray, np.ndarray]


def _weights(rng: np.random.Generator, m: int) -> np.ndarray:
    return rng.uniform(1.0, 10.0, size=m).astype(np.float32)


def _canonical(n: int, src, dst, rng: np.random.Generator) -> Edges:
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    key = np.unique(lo * n + hi)
    lo, hi = key // n, key % n
    return n, lo.astype(np.int32), hi.astype(np.int32), _weights(rng, len(key))


def mesh2d(spec: dict) -> Edges:
    """Triangulated ``side x side`` grid: the FEM-mesh class (NACA0015)."""
    side = int(spec["side"])
    rng = np.random.default_rng(int(spec["graph_seed"]))
    idx = np.arange(side * side).reshape(side, side)
    pairs = [(idx[:, :-1], idx[:, 1:]),      # right
             (idx[:-1, :], idx[1:, :]),      # down
             (idx[:-1, :-1], idx[1:, 1:])]   # diagonal
    src = np.concatenate([a.ravel() for a, _ in pairs])
    dst = np.concatenate([b.ravel() for _, b in pairs])
    return _canonical(side * side, src, dst, rng)


def barabasi_albert(spec: dict) -> Edges:
    """Preferential attachment with ``attach`` edges per new vertex: the
    power-law class (com-DBLP), as networkx's ``barabasi_albert_graph``
    makes it from ``graph_seed`` (connected by construction)."""
    import networkx as nx

    n, k = int(spec["n"]), int(spec["attach"])
    seed = int(spec["graph_seed"])
    e = np.asarray(nx.barabasi_albert_graph(n, k, seed=seed).edges(),
                   dtype=np.int64)
    return _canonical(n, e[:, 0], e[:, 1], np.random.default_rng(seed))


FAMILIES: Dict[str, Callable[[dict], Edges]] = {
    "mesh2d": mesh2d,
    "barabasi_albert": barabasi_albert,
}


def generate(graph_spec: dict) -> Edges:
    """Edge arrays of a configuration's ``graph`` block."""
    family = graph_spec["family"]
    if family not in FAMILIES:
        raise ValueError(f"unknown graph family {family!r}; "
                         f"have {sorted(FAMILIES)}")
    return FAMILIES[family](graph_spec)
