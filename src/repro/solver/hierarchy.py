"""Multilevel pdGRASS: recursive sparsify -> contract -> re-sparsify.

The pdGRASS sparsifier is a preconditioner, not an end product, and it
composes (SF-GRASS, Zhang et al. 2020): the sparsifier of a graph is itself
a graph that can be contracted by heavy-edge matching and sparsified again.
Recursing until the graph is tiny yields a chain of ultra-sparse Laplacians

    L_0 (sparsifier of G)  ->  L_1 (sparsifier of contract(L_0))  ->  ...

that :mod:`repro.solver.device_pcg` applies as a symmetric V-cycle — a
forward fine-to-coarse sweep (smooth, restrict), a tiny dense solve at the
coarsest level, and a backward coarse-to-fine sweep (prolong, smooth).  The
apply is O(sum_l m_l) = O(m) and fully jittable, replacing the dense
Cholesky preconditioner of ``pcg_jax`` which is O(n^3)/O(n^2) and cannot
scale past a few thousand vertices.

Every level stores its Laplacian in the ELL [n, L] slab layout of
``kernels/spmv_ell.py`` so the per-level matvecs route through the same
Pallas kernel as the outer PCG loop.

Contraction runs in one of two modes (``build_hierarchy(contraction=...)``):

  * ``"device"`` (default) — a jit'd heavy-edge propose/accept matching
    with heaviest-neighbor absorption, composed from the
    :mod:`repro.core.graph_ops` primitives and operating on the
    sparsifier's :class:`DeviceGraph` end to end.  No per-edge host Python
    loops anywhere; the only host materializations per level are the
    coalesced coarse edge list (one vectorized ``build_graph`` to seed the
    next level's pipeline run) and, at the bottom, the dense coarse
    Cholesky factor.
  * ``"host"`` — the original sequential greedy matching over numpy
    arrays, kept as the parity oracle.  Both modes follow the same strict
    (weight, -edge id) total order, so they produce the *identical*
    clustering — the device path is the host path with its serial data
    dependencies replaced by propose/accept rounds, exactly the pdGRASS
    move applied to the hierarchy build.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.device_graph import DeviceGraph
from repro.core.graph import Graph, build_graph
from repro.core.graph_ops import (coalesce_edges, propose_accept_matching,
                                  segment_argmax,
                                  sharded_coalesce_edges, sharded_matching,
                                  sharded_segment_argmax)
from repro.obs import get_metrics, get_tracer
from repro.obs.device import trace_annotation
from repro.pipeline import Pipeline, PipelineConfig, pdgrass_config


@dataclasses.dataclass(frozen=True)
class Level:
    """One fine level of the hierarchy (everything above the coarsest).

    Attributes:
      n:        vertex count at this level.
      idx/val:  ELL [n, L] slabs of this level's *sparsifier* Laplacian.
      diag:     [n] weighted degrees (Laplacian diagonal) — Jacobi smoother.
      agg:      [n] int32 coarse vertex id of each fine vertex (restriction/
                prolongation operator in index form: P[i, agg[i]] = 1).
      n_coarse: vertex count of the next level.
      stats:    per-level build statistics.
    """

    n: int
    idx: jnp.ndarray
    val: jnp.ndarray
    diag: jnp.ndarray
    agg: jnp.ndarray
    n_coarse: int
    stats: dict


@dataclasses.dataclass(frozen=True)
class Hierarchy:
    """A multilevel preconditioner chain: fine levels + coarsest dense factor."""

    levels: Tuple[Level, ...]
    coarse_n: int
    coarse_chol: Optional[jnp.ndarray]  # [coarse_n-1, coarse_n-1] lower factor
    coarse_stats: dict

    @property
    def stats(self) -> Tuple[dict, ...]:
        return tuple(lev.stats for lev in self.levels) + (self.coarse_stats,)

    @property
    def depth(self) -> int:
        return len(self.levels) + 1

    @property
    def level_sizes(self) -> list:
        return [lev.n for lev in self.levels] + [self.coarse_n]

    def arrays(self) -> tuple:
        """The device arrays of the chain as a pytree — what a jitted solve
        takes as an argument (see :meth:`with_arrays`)."""
        return (tuple((lev.idx, lev.val, lev.diag, lev.agg)
                      for lev in self.levels), self.coarse_chol)

    def with_arrays(self, arrays: tuple) -> "Hierarchy":
        """This hierarchy's static structure over ``arrays`` (the pytree of
        :meth:`arrays`, possibly traced)."""
        levels, chol = arrays
        return dataclasses.replace(self, coarse_chol=chol, levels=tuple(
            dataclasses.replace(lev, idx=i, val=v, diag=d, agg=a)
            for lev, (i, v, d, a) in zip(self.levels, levels)))


def subgraph(g: Graph, edge_mask: np.ndarray) -> Graph:
    """The graph induced by keeping ``edge_mask`` edges (must stay connected,
    which any pdGRASS sparsifier is — it contains a spanning tree)."""
    keep = np.asarray(edge_mask, dtype=bool)
    return build_graph(g.n, g.src[keep], g.dst[keep], g.weight[keep])


def heavy_edge_matching(g: Graph) -> np.ndarray:
    """Greedy maximal matching preferring heavy edges (host parity oracle).

    Returns ``mate[v]`` = matched partner of v, or -1.  Heavy edges are the
    spectrally important ones (they dominate the Laplacian quadratic form),
    so collapsing them first keeps the coarse graph spectrally close.

    The serving path uses :func:`device_matching` — the propose/accept
    reformulation of this exact scan (same strict total order, same
    matching); this sequential version stays as the reference that the
    device path is tested against.
    """
    order = np.argsort(-g.weight, kind="stable")
    mate = np.full(g.n, -1, dtype=np.int64)
    # The greedy scan is inherently sequential; run it over python ints
    # (one .tolist() each) rather than per-edge numpy scalar extraction —
    # ~an order of magnitude less interpreter overhead on 1e5+ edge levels.
    src_l = g.src[order].tolist()
    dst_l = g.dst[order].tolist()
    mate_l = mate.tolist()
    for u, v in zip(src_l, dst_l):
        if mate_l[u] < 0 and mate_l[v] < 0:
            mate_l[u] = v
            mate_l[v] = u
    return np.asarray(mate_l, dtype=np.int64)


def contract(g: Graph) -> Tuple[np.ndarray, Graph]:
    """Contract a heavy-edge matching into clusters: returns (agg [n] ->
    coarse id, coarse graph).  Host parity oracle for
    :func:`device_contract`.

    Matched pairs seed the clusters; every unmatched vertex then joins its
    heaviest neighbor's cluster (the matching is maximal, so every neighbor
    of an unmatched vertex is matched).  This guarantees coarse_n = #pairs
    <= n/2 per level even on hub graphs, where pairwise-only contraction
    stalls (one pair per level on a star) and would push a nearly-unshrunk
    graph into the dense coarse factor.  Parallel coarse edges are summed
    by ``build_graph`` (Laplacian semantics); intra-cluster edges drop.
    """
    mate = heavy_edge_matching(g)
    agg = np.full(g.n, -1, dtype=np.int64)
    # Matched pairs seed the clusters (vectorized: number the pair's lower
    # endpoint, mirror onto its mate).
    verts = np.arange(g.n)
    lo_end = np.flatnonzero((mate >= 0) & (verts < mate))
    agg[lo_end] = np.arange(lo_end.shape[0])
    agg[mate[lo_end]] = np.arange(lo_end.shape[0])
    nxt = lo_end.shape[0]
    # Unmatched vertices join their heaviest neighbor's cluster.  Heaviest
    # neighbor per vertex, vectorized: sort directed slots by (head, -w);
    # the first slot of each CSR row is then that row's heaviest edge.
    un = agg < 0
    if np.any(un):
        deg = np.diff(g.indptr)
        heads = np.repeat(verts, deg)
        slot_order = np.lexsort((-g.adj_w, heads))[::-1]
        # reversed fancy assignment: the last write per head is its first
        # (heaviest, CSR-order tie-broken) slot in the forward order
        best = np.full(g.n, -1, dtype=np.int64)
        best[heads[slot_order]] = g.adj[slot_order]
        # maximal matching => every neighbor of an unmatched vertex is
        # matched, so agg[best[un]] is always >= 0 on connected graphs
        agg[un] = agg[best[un]]
    cu, cv = agg[g.src], agg[g.dst]
    keep = cu != cv
    coarse = build_graph(nxt, cu[keep], cv[keep], g.weight[keep])
    return agg.astype(np.int32), coarse


@functools.partial(jax.jit, static_argnums=0)
def _device_contract_arrays(n: int, src, dst, weight):
    """jit'd matching + clustering + edge coalesce over flat device arrays.

    Returns ``(mate, agg, n_pairs, csrc, cdst, cw, m_coarse)`` — all device
    arrays, shapes static in (n, m); only ``n_pairs``/``m_coarse`` are read
    back (they are shapes of the next level, necessarily concrete).
    """
    m = src.shape[0]
    verts = jnp.arange(n, dtype=jnp.int32)
    mate = propose_accept_matching(n, src, dst, weight)
    matched = mate >= 0
    # Matched pairs seed the clusters, numbered by their lower endpoint —
    # the same order the host oracle assigns.
    is_lo = matched & (verts < mate)
    pid = jnp.cumsum(is_lo.astype(jnp.int32)) - 1
    pair_of = jnp.where(is_lo, pid, pid[jnp.where(matched, mate, 0)])
    pair_of = jnp.where(matched, pair_of, -1)
    # Unmatched vertices absorb into their heaviest neighbor's cluster
    # (maximal matching => that neighbor is matched).  The concat layout
    # [src-side | dst-side] makes the default element-index tie-break
    # reproduce the host CSR slot order exactly.
    heads = jnp.concatenate([src, dst])
    tails = jnp.concatenate([dst, src])
    w2 = jnp.concatenate([weight, weight])
    pick, _ = segment_argmax(w2, heads, n)
    target = tails[jnp.where(pick < 2 * m, pick, 0)]
    agg = jnp.where(matched, pair_of, pair_of[target])
    csrc, cdst, cw, m_coarse = coalesce_edges(src, dst, weight, agg, n)
    return mate, agg, is_lo.sum(), csrc, cdst, cw, m_coarse


def device_matching(dg: DeviceGraph) -> jnp.ndarray:
    """Heavy-edge maximal matching on the device; ``mate[v]`` int32 or -1.

    Propose/accept rounds under the strict (weight, -edge id) total order —
    bit-for-bit equal to :func:`heavy_edge_matching` on the same graph.
    """
    return propose_accept_matching(dg.n, dg.src, dg.dst, dg.weight)


def device_contract(dg: DeviceGraph) -> Tuple[jnp.ndarray, Graph]:
    """Device counterpart of :func:`contract`: (agg [n] device int32, coarse
    host Graph).

    Matching, cluster aggregation and edge relabel+coalesce all run inside
    one jit'd function of flat device arrays; the host only slices the
    coalesced coarse edge list (already unique and canonical) to build the
    next level's :class:`Graph` — a vectorized ``build_graph``, no per-edge
    Python loops.
    """
    _, agg, n_pairs, csrc, cdst, cw, m_coarse = _device_contract_arrays(
        dg.n, dg.src, dg.dst, dg.weight)
    nc, mc = int(n_pairs), int(m_coarse)
    coarse = build_graph(nc, np.asarray(csrc[:mc]), np.asarray(cdst[:mc]),
                         np.asarray(cw[:mc]))
    return agg, coarse


def _sharded_contract_core(n: int, m_total: int, axis: str):
    """Build the shard_map body for one contraction round: matching +
    clustering + two-phase coalesce, edges sharded over ``axis``.

    Local args are the shard's edge slice (``eids`` global edge ids, -1 on
    padding; padding slots carry ``src == dst == 0`` so the coalesce drops
    them).  Outputs are replicated.  The clustering math is the replicated
    [n]-array mirror of :func:`_device_contract_arrays` — same pair
    numbering, same concat slot order for the absorption tie-break — so the
    sharded rounds produce the *identical* agg the device (and host) paths
    do.
    """

    def fn(src, dst, weight, eids):
        verts = jnp.arange(n, dtype=jnp.int32)
        valid = eids >= 0
        mate = sharded_matching(n, src, dst, weight, eids, axis=axis)
        matched = mate >= 0
        is_lo = matched & (verts < mate)
        pid = jnp.cumsum(is_lo.astype(jnp.int32)) - 1
        pair_of = jnp.where(is_lo, pid, pid[jnp.where(matched, mate, 0)])
        pair_of = jnp.where(matched, pair_of, -1)
        # Unmatched vertices absorb into their heaviest neighbor's cluster.
        # Global slot ids reproduce the device path's [src-side | dst-side]
        # concat layout: src-side slot of edge e is e, dst-side is
        # m_total + e — the pmin tie-break then matches the element-index
        # tie-break of the single-device segment_argmax exactly.
        heads = jnp.concatenate([src, dst])
        tails = jnp.concatenate([dst, src])
        slots = jnp.concatenate(
            [eids, jnp.where(valid, eids + m_total, -1)])
        w2 = jnp.where(jnp.concatenate([valid, valid]),
                       jnp.concatenate([weight, weight]), -jnp.inf)
        big = jnp.iinfo(jnp.int32).max
        pick, _ = sharded_segment_argmax(w2, heads, n, axis=axis,
                                         element_ids=slots, sentinel=big)
        # resolve tails[pick] across shards: the shard owning the winning
        # slot scatters its tail; pmax merges (one winner per vertex).
        won = (slots >= 0) & (pick[heads] == slots)
        tgt = jnp.full((n,), -1, jnp.int32).at[
            jnp.where(won, heads, n)].set(
            jnp.where(won, tails, 0), mode="drop")
        tgt = jax.lax.pmax(tgt, axis)
        agg = jnp.where(matched, pair_of,
                        pair_of[jnp.where(tgt >= 0, tgt, 0)])
        csrc, cdst, cw, m_coarse = sharded_coalesce_edges(
            src, dst, weight, agg, n, axis=axis)
        return mate, agg, is_lo.sum(), csrc, cdst, cw, m_coarse

    return fn


def sharded_contract(dg: DeviceGraph, mesh, axis: str = "data"
                     ) -> Tuple[jnp.ndarray, Graph]:
    """Mesh-sharded counterpart of :func:`device_contract`: the
    propose/accept rounds run under ``shard_map`` with the edge list
    row-sharded over ``axis``.

    Returns ``(agg [n] replicated device int32, coarse host Graph)`` — the
    identical clustering the device path produces (the strict total order
    survives the collectives), with coarse weights equal up to f32 sum
    order (the two-phase coalesce sums per shard first).
    """
    n_sh = int(mesh.shape[axis])
    m = dg.m
    m_loc = max(1, -(-m // n_sh))
    m_pad = m_loc * n_sh

    def pad(x, fill, dtype):
        out = np.full((m_pad,), fill, dtype)
        out[:m] = np.asarray(x)
        return jnp.asarray(out)

    src_p = pad(dg.src, 0, np.int32)
    dst_p = pad(dg.dst, 0, np.int32)
    w_p = pad(dg.weight, 0.0, np.float32)
    eids = pad(np.arange(m, dtype=np.int32), -1, np.int32)

    # check_vma=False: the coarse edge list is an all_gather followed by
    # the same merge on every shard, so it is replicated by construction,
    # but its type stays "varying" (jax has no public invariant all_gather)
    # and the default check would refuse the P() out_specs.
    fn = jax.shard_map(
        _sharded_contract_core(dg.n, m, axis), mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P(), P(), P(), P(), P()), check_vma=False)
    _, agg, n_pairs, csrc, cdst, cw, m_coarse = fn(src_p, dst_p, w_p, eids)
    nc, mc = int(n_pairs), int(m_coarse)
    coarse = build_graph(nc, np.asarray(csrc[:mc]), np.asarray(cdst[:mc]),
                         np.asarray(cw[:mc]))
    return agg, coarse


def _laplacian_diag(g: Graph) -> np.ndarray:
    deg = np.zeros(g.n, dtype=np.float64)
    np.add.at(deg, g.src, g.weight)
    np.add.at(deg, g.dst, g.weight)
    return deg


def _grounded_chol(g: Graph) -> Optional[jnp.ndarray]:
    """Lower Cholesky factor of the grounded (node-0-removed) Laplacian."""
    if g.n < 2:
        return None
    w = g.weight.astype(np.float64)
    L = np.zeros((g.n, g.n), dtype=np.float64)
    np.add.at(L, (g.src, g.dst), -w)
    np.add.at(L, (g.dst, g.src), -w)
    L[np.arange(g.n), np.arange(g.n)] = _laplacian_diag(g)
    return jnp.asarray(np.linalg.cholesky(L[1:, 1:]).astype(np.float32))


def build_hierarchy(
    graph: Graph,
    alpha: float = 0.05,
    *,
    config: Optional[PipelineConfig] = None,
    coarse_n: int = 64,
    max_levels: int = 16,
    chunk: int = 512,
    contraction: str = "device",
    mesh=None,
    shard_axis: str = "data",
    **pdgrass_kwargs,
) -> Hierarchy:
    """Sparsify/contract recursively until the graph fits a dense coarse solve.

    Each level sparsifies through the staged :class:`repro.pipeline.Pipeline`
    (``config`` if given — any family member works, feGRASS included —
    else a pdGRASS config from ``alpha``/``chunk``/``pdgrass_kwargs``),
    stores the sparsifier Laplacian in ELL form via the device-resident
    ``Sparsifier.to_ell()`` path (no scipy), then contracts the sparsifier
    by heavy-edge matching to produce the next level's graph.  Vertex counts
    shrink by the matching ratio (~2x on meshes) every level, so the chain
    has O(log n) levels and O(m) total edges.

    ``contraction`` selects the matching/contraction implementation:
    ``"device"`` (default) runs the jit'd propose/accept path of
    :func:`device_contract` on the sparsifier's :class:`DeviceGraph`;
    ``"host"`` runs the sequential greedy oracle :func:`contract`;
    ``"sharded"`` runs :func:`sharded_contract` — the propose/accept
    rounds under ``shard_map`` with the edge list sharded over
    ``mesh``/``shard_axis`` (required for this mode).  All three follow
    the same strict total order and produce the same clustering — the host
    path exists for parity testing and as the no-JAX fallback; the sharded
    path is what lets a 1e6+-vertex build compose with the distributed
    solve on one mesh.
    """
    if contraction not in ("device", "host", "sharded"):
        raise ValueError(
            f"unknown contraction mode {contraction!r}; "
            f"want 'device', 'host' or 'sharded'")
    if contraction == "sharded" and mesh is None:
        raise ValueError("contraction='sharded' needs a mesh")
    if config is None:
        config = pdgrass_config(alpha=alpha, chunk=chunk, **pdgrass_kwargs)
    pipe = Pipeline(config)
    tracer = get_tracer()
    levels = []
    g = graph
    with tracer.span("hierarchy.build", contraction=contraction,
                     n=graph.n, m=graph.m) as build_span:
        for _ in range(max_levels):
            if g.n <= coarse_n:
                break
            with tracer.span("hierarchy.level", level=len(levels),
                             n=g.n, m=g.m) as lev_span:
                m_off = g.m - (g.n - 1)
                if m_off > 0:
                    with tracer.span("hierarchy.sparsify", n=g.n, m=g.m):
                        sp = pipe.run(g)
                    edge_mask = sp.edge_mask
                    dg = sp.device_graph
                else:
                    edge_mask = None  # already a tree — nothing to sparsify
                    dg = DeviceGraph.from_graph(g)
                with tracer.span("hierarchy.contract", mode=contraction), \
                        trace_annotation(f"hierarchy.contract.{contraction}"):
                    if contraction == "device":
                        agg_dev, coarse = device_contract(dg)
                        m_sparsifier = dg.m
                    elif contraction == "sharded":
                        agg_dev, coarse = sharded_contract(
                            dg, mesh, axis=shard_axis)
                        m_sparsifier = dg.m
                    else:
                        sg = subgraph(g, edge_mask) \
                            if edge_mask is not None else g
                        agg_host, coarse = contract(sg)
                        agg_dev = jnp.asarray(agg_host)
                        m_sparsifier = sg.m
                lev_span.set(n_coarse=coarse.n)
            if coarse.n >= g.n:  # no progress — stop rather than loop
                break
            idx, val = dg.to_ell()
            lev_stats = {
                "n": g.n, "m": g.m, "m_sparsifier": m_sparsifier,
                "n_coarse": coarse.n, "shrink": coarse.n / g.n,
                "contraction": contraction,
            }
            levels.append(Level(
                n=g.n, idx=idx, val=val, diag=dg.diag,
                agg=agg_dev, n_coarse=coarse.n, stats=lev_stats,
            ))
            g = coarse
        coarse_stats = {"n": g.n, "m": g.m, "m_sparsifier": g.m,
                        "n_coarse": g.n, "shrink": 1.0,
                        "contraction": contraction}
        with tracer.span("hierarchy.coarse_chol", n=g.n):
            chol = _grounded_chol(g)
        build_span.set(depth=len(levels) + 1)
    m = get_metrics()
    m.inc("hierarchy.builds")
    m.inc("hierarchy.levels_built", len(levels))
    m.set_gauge("hierarchy.last_depth", len(levels) + 1)
    return Hierarchy(levels=tuple(levels), coarse_n=g.n,
                     coarse_chol=chol, coarse_stats=coarse_stats)
