"""Reusable jit-safe device primitives for label/propose/accept graph work.

pdGRASS's core claim is that propose/accept-style parallelism removes the
serial data dependencies of greedy graph algorithms.  The repo uses that
pattern in two places — Boruvka spanning trees (``core/spanning_tree``) and
heavy-edge contraction (``solver/hierarchy``) — and both decompose into the
same handful of flat-array primitives, collected here:

  * :func:`segment_argmax`        — deterministic per-segment argmax with a
    (value, min element-id) total order, the "every component picks its best
    edge" step of Boruvka and the "every vertex picks its heaviest incident
    edge" step of matching.
  * :func:`handshake`             — the symmetric accept: an edge wins iff
    *both* of its endpoints proposed it.
  * :func:`propose_accept_matching` — locally-dominant heavy-edge matching
    built from the two above.  With a strict (weight, -edge id) total order
    this provably equals the *sequential* greedy matching, so the host
    oracle and the device path agree bit-for-bit.
  * :func:`pointer_jump`          — pointer-jumping label collapse
    (parent forest -> roots in O(log depth) doubling steps).
  * :func:`compact_labels`        — order-preserving dense relabel of a
    sparse label set (component roots -> 0..k-1).
  * :func:`coalesce_edges`        — segmented edge relabel + merge: push an
    edge list through a vertex labeling, drop intra-cluster edges, sum
    parallel edges — the contraction step, entirely on the device.

Everything here is shape-static ``jnp`` scatter/gather/sort work: safe
under ``jit``, free of host round-trips, and padded with explicit
sentinels rather than dynamic shapes.

Mesh-aware variants (for bodies running under ``shard_map`` with the
edge arrays row-sharded over a named axis) sit beside their single-device
counterparts: :func:`sharded_segment_argmax` combines per-shard argmaxes
with a ``pmax``/``pmin`` pair under the same (value, min element-id) total
order, :func:`sharded_matching` is :func:`propose_accept_matching` with
its per-round segment sweep distributed, and :func:`sharded_coalesce_edges`
is a two-phase (local combine, ``all_gather``, final merge) contraction.
All three are *bit-identical* to the single-device primitives on the same
input — the strict total order survives the collectives — which is what
lets the sharded hierarchy build serve as a drop-in for the device one.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

def segment_argmax(values: jnp.ndarray, segment_ids: jnp.ndarray,
                   num_segments: int, *,
                   element_ids: Optional[jnp.ndarray] = None,
                   sentinel: Optional[int] = None,
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-segment argmax under the (value, minimal element id) total order.

    Returns ``(pick, best)`` with ``pick[s]`` the winning element id of
    segment ``s`` and ``best[s]`` its value.  Deterministic: among
    value-maximal elements the *smallest* element id wins.  ``element_ids``
    defaults to ``arange(len(values))``; passing custom ids lets duplicated
    entries (e.g. both directions of an undirected edge) resolve to one
    winner.  Segments that are empty — or whose values are all ``-inf``,
    the conventional "masked out" encoding — get ``pick == sentinel``
    (default: ``len(values)``) and ``best == -inf``.  Out-of-range
    ``segment_ids`` (e.g. ``-1`` padding) are dropped.
    """
    k = values.shape[0]
    if element_ids is None:
        element_ids = jnp.arange(k, dtype=jnp.int32)
    if sentinel is None:
        sentinel = k
    # Negative ids would *wrap* under jnp indexing; push them past the end
    # so the scatters genuinely drop them.
    segs = jnp.where(segment_ids < 0, num_segments, segment_ids)
    best = jnp.full((num_segments,), -jnp.inf, dtype=values.dtype)
    best = best.at[segs].max(values, mode="drop")
    # The gather clips out-of-range segs to the last segment, which can mark
    # a dropped element "best" — harmless: its pick scatter drops too.
    is_best = (values == best[segs]) & (values > -jnp.inf)
    # Only best elements scatter (non-best ones are routed out of bounds and
    # dropped) and the reduction starts from the dtype max, so the min never
    # mixes element ids with the sentinel — any sentinel value works,
    # including ones below the ids (e.g. -1).  Untouched segments are mapped
    # to the sentinel afterwards.
    big = jnp.iinfo(element_ids.dtype).max
    pick = jnp.full((num_segments,), big, dtype=element_ids.dtype)
    pick = pick.at[jnp.where(is_best, segs, num_segments)].min(
        element_ids, mode="drop")
    pick = jnp.where(pick == big, sentinel, pick)
    return pick, best


def handshake(prop: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray
              ) -> jnp.ndarray:
    """Symmetric accept round: edge ``e`` wins iff both endpoints propose it.

    ``prop[v]`` is the edge id vertex ``v`` proposes (any sentinel >= m for
    "no proposal").  Returns the ``[m]`` bool mask of mutually-proposed
    edges.  Accepted edges are vertex-disjoint by construction: a vertex
    proposes at most one edge.
    """
    e = jnp.arange(src.shape[0], dtype=prop.dtype)
    return (prop[src] == e) & (prop[dst] == e)


def pointer_jump(parent: jnp.ndarray) -> jnp.ndarray:
    """Collapse a parent forest to its roots: ``p[v] -> root(v)``.

    Doubling (``p = p[p]``) until fixpoint — O(log depth) gather sweeps.
    The forest must be cycle-free apart from root self-loops.
    """
    def body(p):
        return p[p]

    def cond(p):
        return jnp.any(p[p] != p)

    return jax.lax.while_loop(cond, body, parent)


def compact_labels(labels: jnp.ndarray, num_labels: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Order-preserving dense relabel: sparse ids in [0, num_labels) -> 0..k-1.

    Returns ``(dense, k)`` where ``k`` is the number of distinct labels and
    ``dense`` preserves the original ``<`` order (label compaction after
    pointer-jumping: component roots become consecutive coarse ids).
    """
    used = jnp.zeros((num_labels,), jnp.int32).at[labels].set(1, mode="drop")
    new_id = (jnp.cumsum(used) - 1).astype(labels.dtype)
    return new_id[labels], used.sum()


def propose_accept_matching(n: int, src: jnp.ndarray, dst: jnp.ndarray,
                            weight: jnp.ndarray) -> jnp.ndarray:
    """Heavy-edge maximal matching by propose/accept rounds; ``mate[v]`` or -1.

    Every round, each free vertex proposes its heaviest incident *alive*
    edge (both endpoints free) under the strict (weight, -edge id) total
    order; mutually-proposed (locally dominant) edges match.  The globally
    heaviest alive edge is always locally dominant, so every round makes
    progress and the loop terminates with a maximal matching.

    Because the total order is strict, the result is exactly the matching
    the *sequential* greedy scan over edges sorted by descending
    (weight, -edge id) produces — the host oracle in
    ``solver/hierarchy.heavy_edge_matching`` — with all serial data
    dependencies replaced by O(rounds) flat segment-argmax sweeps.
    """
    m = src.shape[0]
    eidx = jnp.arange(m, dtype=jnp.int32)
    heads = jnp.concatenate([src, dst])
    eids2 = jnp.concatenate([eidx, eidx])
    w2 = jnp.concatenate([weight, weight])

    def body(state):
        mate, _ = state
        free = mate < 0
        alive = free[src] & free[dst]
        alive2 = jnp.concatenate([alive, alive])
        vals = jnp.where(alive2, w2, -jnp.inf)
        prop, _ = segment_argmax(vals, heads, n, element_ids=eids2,
                                 sentinel=m)
        accept = handshake(prop, src, dst)
        mate = mate.at[jnp.where(accept, src, n)].set(
            jnp.where(accept, dst, 0), mode="drop")
        mate = mate.at[jnp.where(accept, dst, n)].set(
            jnp.where(accept, src, 0), mode="drop")
        return mate, jnp.any(alive)

    mate0 = jnp.full((n,), -1, dtype=jnp.int32)
    mate, _ = jax.lax.while_loop(lambda s: s[1], body,
                                 (mate0, jnp.bool_(True)))
    return mate


def coalesce_edges(src: jnp.ndarray, dst: jnp.ndarray, weight: jnp.ndarray,
                   labels: jnp.ndarray, num_labels: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                              jnp.ndarray]:
    """Relabel an edge list through ``labels`` and merge the result.

    Intra-cluster edges (both endpoints in the same label) drop; parallel
    coarse edges merge with their weights summed (Laplacian semantics).
    Shape-static: returns ``(csrc, cdst, cw, m_coarse)`` where the arrays
    keep the input length ``m`` and only the first ``m_coarse`` entries are
    valid (canonical ``csrc < cdst``, sorted by (csrc, cdst)); slots beyond
    that hold zeros.  ``num_labels`` bounds the label values (``n`` of the
    fine graph always works); it is accepted for interface symmetry with
    the other segment ops but the lexicographic sort never needs it.
    """
    del num_labels  # kept for API clarity; the sort is label-range-free
    m = src.shape[0]
    cu, cv = labels[src], labels[dst]
    valid = cu != cv
    big = jnp.iinfo(jnp.int32).max
    # Lexicographic (lo, hi) sort — int32-safe at any label range (a fused
    # lo * num_labels + hi key would overflow without x64).  Invalid edges
    # sort to the end via the sentinel.
    lo = jnp.where(valid, jnp.minimum(cu, cv).astype(jnp.int32), big)
    hi = jnp.where(valid, jnp.maximum(cu, cv).astype(jnp.int32), big)
    order = jnp.lexsort((hi, lo))
    lo_s, hi_s = lo[order], hi[order]
    w_s, valid_s = weight[order], valid[order]
    first = valid_s & jnp.concatenate(
        [jnp.ones((1,), bool),
         (lo_s[1:] != lo_s[:-1]) | (hi_s[1:] != hi_s[:-1])])
    uid = jnp.cumsum(first.astype(jnp.int32)) - 1   # coarse edge id per slot
    safe_uid = jnp.where(valid_s, uid, m)
    cw = jnp.zeros((m,), weight.dtype).at[safe_uid].add(
        jnp.where(valid_s, w_s, 0), mode="drop")
    first_uid = jnp.where(first, uid, m)
    csrc = jnp.zeros((m,), jnp.int32).at[first_uid].set(lo_s, mode="drop")
    cdst = jnp.zeros((m,), jnp.int32).at[first_uid].set(hi_s, mode="drop")
    return csrc, cdst, cw, first.sum()


# ---------------------------------------------------------------------------
# Mesh-aware variants: same semantics, edges row-sharded over a named axis.
# Every function below runs INSIDE a shard_map body; its array arguments are
# the local shard slices and its outputs are replicated across the axis.
# ---------------------------------------------------------------------------

def sharded_segment_argmax(values: jnp.ndarray, segment_ids: jnp.ndarray,
                           num_segments: int, *, axis: str,
                           element_ids: jnp.ndarray,
                           sentinel: Optional[int] = None,
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`segment_argmax` with the elements sharded over mesh ``axis``.

    Each shard reduces its local elements, then two collectives combine the
    shards under the same (value, minimal element id) total order: a
    ``pmax`` settles the per-segment best value, a ``pmin`` over the element
    ids that attain it settles the winner.  ``element_ids`` is mandatory and
    must carry *global* ids (unique across shards) — local ``arange`` ids
    would collide between shards and corrupt the tie-break.  The result is
    replicated: every shard holds the full ``[num_segments]`` pick/best.
    """
    big = jnp.iinfo(element_ids.dtype).max
    pick_l, best_l = segment_argmax(values, segment_ids, num_segments,
                                    element_ids=element_ids, sentinel=big)
    best = jax.lax.pmax(best_l, axis)
    cand = jnp.where((best_l == best) & (best > -jnp.inf), pick_l, big)
    pick = jax.lax.pmin(cand, axis)
    if sentinel is None:
        sentinel = big
    return jnp.where(pick == big, sentinel, pick), best


def sharded_matching(n: int, src: jnp.ndarray, dst: jnp.ndarray,
                     weight: jnp.ndarray, edge_ids: jnp.ndarray, *,
                     axis: str) -> jnp.ndarray:
    """:func:`propose_accept_matching` with the edge list sharded over
    ``axis``; returns the replicated ``[n]`` ``mate`` array.

    ``edge_ids`` carries the global edge id of every local slot, ``-1`` for
    padding (shards are padded to equal length).  Each round the proposal
    sweep runs as a :func:`sharded_segment_argmax` (one ``pmax`` + one
    ``pmin``), every shard tests the handshake on its own edges, and the
    accepted writes merge with a ``pmax`` (accepted edges are vertex-
    disjoint across the *whole* mesh, so at most one shard writes a
    vertex).  The strict (weight, -edge id) total order is preserved end to
    end, so the matching is bit-identical to the single-device rounds and
    therefore to the sequential greedy oracle.
    """
    valid = edge_ids >= 0
    heads = jnp.concatenate([src, dst])
    eids2 = jnp.concatenate([edge_ids, edge_ids])
    w2 = jnp.concatenate([weight, weight])
    big = jnp.iinfo(jnp.int32).max

    def body(state):
        mate, _ = state
        free = mate < 0
        alive = valid & free[src] & free[dst]
        alive2 = jnp.concatenate([alive, alive])
        vals = jnp.where(alive2, w2, -jnp.inf)
        prop, _ = sharded_segment_argmax(vals, heads, n, axis=axis,
                                         element_ids=eids2, sentinel=big)
        accept = alive & (prop[src] == edge_ids) & (prop[dst] == edge_ids)
        upd = jnp.full((n,), -1, jnp.int32)
        upd = upd.at[jnp.where(accept, src, n)].set(
            jnp.where(accept, dst, 0), mode="drop")
        upd = upd.at[jnp.where(accept, dst, n)].set(
            jnp.where(accept, src, 0), mode="drop")
        upd = jax.lax.pmax(upd, axis)
        mate = jnp.where(upd >= 0, upd, mate)
        n_alive = jax.lax.psum(jnp.sum(alive.astype(jnp.int32)), axis)
        return mate, n_alive > 0

    mate0 = jnp.full((n,), -1, dtype=jnp.int32)
    mate, _ = jax.lax.while_loop(lambda s: s[1], body,
                                 (mate0, jnp.bool_(True)))
    return mate


def sharded_coalesce_edges(src: jnp.ndarray, dst: jnp.ndarray,
                           weight: jnp.ndarray, labels: jnp.ndarray,
                           num_labels: int, *, axis: str
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                      jnp.ndarray]:
    """:func:`coalesce_edges` with the edge list sharded over ``axis``.

    Two phases, the classic combiner/reduce split: every shard coalesces
    its *local* slice first (one local lexsort — this is where parallel
    duplicates within a shard collapse), then one ``all_gather`` of the
    locally-merged lists feeds a final replicated merge.  Padding slots
    (``src == dst``) drop in phase one.  Output layout matches
    :func:`coalesce_edges` over the gathered length ``n_sh * m_loc``:
    canonical, sorted, first ``m_coarse`` entries valid — replicated on
    every shard.  Coarse weights equal the single-device result up to f32
    summation order (partial sums happen per shard first).
    """
    csrc, cdst, cw, _ = coalesce_edges(src, dst, weight, labels, num_labels)
    g_src = jax.lax.all_gather(csrc, axis, tiled=True)
    g_dst = jax.lax.all_gather(cdst, axis, tiled=True)
    g_w = jax.lax.all_gather(cw, axis, tiled=True)
    # phase two relabels through the identity: entries are already coarse
    # ids; empty slots came out of phase one as (0, 0) and drop again.
    ident = jnp.arange(num_labels, dtype=jnp.int32)
    return coalesce_edges(g_src, g_dst, g_w, ident, num_labels)
