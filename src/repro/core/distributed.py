"""Distributed pdGRASS recovery: the paper's mixed parallel strategy on a mesh.

The paper parallelizes over OpenMP threads; here the same two-level
decomposition maps onto a JAX device mesh with shard_map:

  * **Outer parallelism** (Lemma 7 — subtasks are disjoint): subtasks are
    greedily bin-packed (LPT) onto devices; every device runs the local
    round engine on its own bucket with *zero* communication.  This is the
    embarrassingly-parallel regime the paper exploits on uniform inputs.
  * **Inner parallelism** (skewed inputs — e.g. the com-Youtube giant
    subtask holding >99% of off-tree edges): the edges of one huge subtask
    are sharded contiguously across all devices of the group.  Each round,
    devices select their local candidate prefix, exchange candidate rows
    with a single ``all_gather`` (the only collective), replicate the tiny
    in-block resolution, and mark their local slice.  The loop condition is
    a ``psum`` so all devices agree on termination.
  * **Mixed strategy**: subtasks above ``cutoff`` (paper: 1e5 edges or 10%
    of off-tree edges) go through the inner engine one at a time; the rest
    are bucketed for the outer engine — exactly the heuristic in §IV.A.

The same code paths lower on the production (multi-pod) mesh for the
dry-run: see ``repro.launch.dryrun`` with ``--arch pdgrass_graph``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.obs import get_metrics, get_tracer

from repro.core import recovery as rec_mod
from repro.core.recovery import (STATUS_OPEN, STATUS_RECOVERED,
                                 STATUS_SKIPPED, RecoveryProblem,
                                 strict_similarity_matrix)


# ---------------------------------------------------------------------------
# Host-side partitioning (outer parallelism)
# ---------------------------------------------------------------------------

def pad_fill_value(dtype, *, lowest: bool = False):
    """Per-dtype sentinel for padding slots in the shard builders.

    ``lowest=True`` asks for the most-negative representable value (the
    "never wins an argmax" encoding for score arrays): ``-inf`` for floats,
    ``iinfo.min`` for signed integers.  ``lowest=False`` asks for the
    conventional ``-1`` invalid marker (checked via ``x >= 0`` downstream).
    Unsigned integers cannot represent either sentinel — ``np.full`` would
    silently wrap ``-1`` to the *maximum*, turning padding into live data —
    so they are rejected loudly.
    """
    dtype = np.dtype(dtype)
    if np.issubdtype(dtype, np.floating):
        return -np.inf if lowest else dtype.type(-1.0)
    if np.issubdtype(dtype, np.unsignedinteger):
        raise TypeError(
            f"cannot pad unsigned dtype {dtype}: the -1/-inf sentinels "
            f"would wrap to live values — use a signed or float array")
    if np.issubdtype(dtype, np.integer):
        return np.iinfo(dtype).min if lowest else dtype.type(-1)
    raise TypeError(f"no pad sentinel for dtype {dtype}")


def partition_subtasks(sizes: np.ndarray, n_shards: int,
                       cutoff: int | None = None,
                       cutoff_frac: float = 0.10):
    """LPT bin-packing of subtasks onto shards.

    Returns (shard_of_subtask [S] with -1 = "inner" giant task,
             giant_subtask_ids list, per-shard load).
    """
    total = int(sizes.sum())
    if cutoff is None:
        cutoff = int(min(1e5, max(1, cutoff_frac * total)))
    giants = np.flatnonzero(sizes >= cutoff)
    shard_of = np.full(sizes.shape[0], -1, dtype=np.int32)
    load = np.zeros(n_shards, dtype=np.int64)
    order = np.argsort(-sizes)
    for s in order:
        if sizes[s] >= cutoff:
            continue
        tgt = int(np.argmin(load))
        shard_of[s] = tgt
        load[tgt] += int(sizes[s])
    return shard_of, giants.tolist(), load


class ShardedProblem(NamedTuple):
    """[n_shards, m_loc] stacked per-device recovery problems."""

    sig_u: jnp.ndarray
    sig_v: jnp.ndarray
    beta: jnp.ndarray
    seg: jnp.ndarray
    score: jnp.ndarray
    # maps local rows back to rows of the flat (sorted) problem; -1 = pad
    src_row: jnp.ndarray


def build_outer_shards(problem: RecoveryProblem, seg_sizes: np.ndarray,
                       shard_of: np.ndarray, n_shards: int,
                       chunk: int = 2048) -> ShardedProblem:
    """Materialize per-shard edge buckets (host side, one-time cost)."""
    seg = np.asarray(problem.seg)
    m = seg.shape[0]
    rows_per_shard: list[list[np.ndarray]] = [[] for _ in range(n_shards)]
    # segments are contiguous: locate them once
    starts = np.flatnonzero(np.concatenate([[True], seg[1:] != seg[:-1]]))
    starts = starts[seg[starts] >= 0]
    for st in starts:
        sid = seg[st]
        tgt = shard_of[sid]
        if tgt < 0:
            continue
        rows_per_shard[tgt].append(np.arange(st, st + seg_sizes[sid]))
    m_loc = max([chunk] + [
        int(np.ceil(sum(len(r) for r in rows) / chunk)) * chunk
        for rows in rows_per_shard])

    def gather(x, *, lowest=False):
        x = np.asarray(x)
        fill = pad_fill_value(x.dtype, lowest=lowest)
        out = np.full((n_shards, m_loc) + x.shape[1:], fill, dtype=x.dtype)
        for sh, rows in enumerate(rows_per_shard):
            if rows:
                idx = np.concatenate(rows)
                out[sh, : idx.shape[0]] = x[idx]
        return jnp.asarray(out)

    src_row = np.full((n_shards, m_loc), -1, dtype=np.int64)
    for sh, rows in enumerate(rows_per_shard):
        if rows:
            idx = np.concatenate(rows)
            src_row[sh, : idx.shape[0]] = idx
    return ShardedProblem(
        sig_u=gather(problem.sig_u),
        sig_v=gather(problem.sig_v),
        beta=gather(problem.beta),
        seg=gather(problem.seg),
        score=gather(problem.score, lowest=True),
        src_row=jnp.asarray(src_row),
    )


# ---------------------------------------------------------------------------
# Outer engine: shard_map over the stacked buckets (no collectives)
# ---------------------------------------------------------------------------

def recover_outer(sharded: ShardedProblem, mesh, axis: str = "data",
                  block_size: int = 16, max_candidates: int = 128,
                  chunk: int = 2048):
    """Run the local round engine on every shard (embarrassingly parallel)."""

    def local(sig_u, sig_v, beta, seg, score):
        prob = RecoveryProblem(sig_u[0], sig_v[0], beta[0], seg[0], score[0])
        status, stats = rec_mod.recover_rounds(
            prob, block_size=block_size, max_candidates=max_candidates,
            stop_at_target=False, chunk=chunk)
        return status[None], stats.rounds[None]

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)))
    status, rounds = fn(sharded.sig_u, sharded.sig_v, sharded.beta,
                        sharded.seg, sharded.score)
    return status, rounds


# ---------------------------------------------------------------------------
# Inner engine: one giant subtask sharded across devices
# ---------------------------------------------------------------------------

def _inner_round_engine(sig_u, sig_v, beta, seg, axis: str, n_sh: int,
                        block_size: int, chunk: int):
    """Round engine for one segment sharded over ``axis``.

    Local shapes: sig_u/sig_v [m_loc, c1]; beta/seg [m_loc].
    One all_gather of candidate rows per round; psum for termination.

    ``n_sh`` is the *static* shard count along ``axis``, supplied by the
    :func:`recover_inner` wrapper (which reads ``mesh.shape[axis]``).  It
    must be static: the engine builds ``jnp.arange(n_sh)`` and reshapes
    gathered blocks by it, neither of which traces from a dynamic value.
    """
    m_loc = seg.shape[0]
    c1 = sig_u.shape[1]
    B = block_size
    my = jax.lax.axis_index(axis)
    is_edge = seg >= 0
    status0 = jnp.where(is_edge, STATUS_OPEN, STATUS_SKIPPED).astype(jnp.int8)
    arange = jnp.arange(m_loc, dtype=jnp.int32)

    def cond(state):
        status, _ = state
        n_open = jnp.sum((status == STATUS_OPEN).astype(jnp.int32))
        return jax.lax.psum(n_open, axis) > 0

    def body(state):
        status, rounds = state
        avail = status == STATUS_OPEN
        ones = avail.astype(jnp.int32)
        local_cum = jnp.cumsum(ones)
        local_tot = local_cum[-1]
        # exclusive prefix over shards of open counts
        all_tot = jax.lax.all_gather(local_tot, axis)          # [n_sh]
        base = jnp.sum(jnp.where(jnp.arange(n_sh) < my, all_tot, 0))
        rank = base + local_cum - ones                         # global rank
        cand = avail & (rank < B)

        # collect local candidates (<= B), then all_gather
        cidx = jnp.sort(jnp.where(cand, arange, m_loc))[:B]
        cvalid = cidx < m_loc
        ci = jnp.where(cvalid, cidx, 0)
        crank = jnp.where(cvalid, rank[ci], B)
        pack = (sig_u[ci], sig_v[ci],
                jnp.where(cvalid, beta[ci], -1), crank)
        g_su, g_sv, g_beta, g_rank = jax.lax.all_gather(pack, axis)  # [n_sh, B, ...]
        g_su = g_su.reshape(n_sh * B, c1)
        g_sv = g_sv.reshape(n_sh * B, c1)
        g_beta = g_beta.reshape(n_sh * B)
        g_rank = g_rank.reshape(n_sh * B)
        # order by global rank; invalid slots have rank == B -> sorted last
        order = jnp.argsort(g_rank, stable=True)[:B]
        k_su, k_sv = g_su[order], g_sv[order]
        k_beta, k_rank = g_beta[order], g_rank[order]
        k_valid = k_beta >= 0

        # replicated in-block resolution (deterministic on every shard)
        sim = strict_similarity_matrix(k_su, k_sv, k_beta, k_su, k_sv)
        later = jnp.arange(B)[None, :] > jnp.arange(B)[:, None]
        sim = sim & later & k_valid[:, None] & k_valid[None, :]

        def scan_body(killed, row):
            sim_row, idx = row
            alive = ~killed[idx]
            return killed | jnp.where(alive, sim_row, False), None

        killed, _ = jax.lax.scan(scan_body, jnp.zeros_like(sim[0]),
                                 (sim, jnp.arange(B)))
        recovered_k = k_valid & ~killed

        # write back statuses for MY candidates (match by global rank)
        my_new = jnp.zeros((B,), jnp.int8)
        # k_rank -> status; map each of my cand slots to its rank row
        hit = crank[:, None] == k_rank[None, :]      # [B_my, B_k]
        rec_my = jnp.any(hit & recovered_k[None, :], axis=1)
        status = status.at[jnp.where(cvalid, cidx, m_loc)].set(
            jnp.where(rec_my, STATUS_RECOVERED, STATUS_SKIPPED).astype(jnp.int8),
            mode="drop")

        # mark local open rows vs recovered block rows
        mark_beta = jnp.where(recovered_k, k_beta, -1)

        def mark_chunk(start):
            esu = jax.lax.dynamic_slice(sig_u, (start, 0), (chunk, c1))
            esv = jax.lax.dynamic_slice(sig_v, (start, 0), (chunk, c1))
            sim_mk = strict_similarity_matrix(k_su, k_sv, mark_beta, esu, esv)
            return jnp.any(sim_mk, axis=0)

        kill = jax.lax.map(
            mark_chunk, jnp.arange(m_loc // chunk, dtype=jnp.int32) * chunk
        ).reshape(m_loc)
        kill = kill & (status == STATUS_OPEN) & is_edge
        status = jnp.where(kill, STATUS_SKIPPED, status).astype(jnp.int8)
        return status, rounds + 1

    status, rounds = jax.lax.while_loop(
        cond, body, (status0, jnp.int32(0)))
    return status, rounds


def recover_inner(sig_u, sig_v, beta, seg, mesh, axis: str = "data",
                  block_size: int = 32, chunk: int = 2048):
    """shard_map wrapper for one giant segment sharded over ``axis``.

    The wrapper knows the mesh, so the shard count goes in as a static
    Python int — the engine never derives it from collectives."""
    fn = jax.shard_map(
        functools.partial(_inner_round_engine, axis=axis,
                          n_sh=int(mesh.shape[axis]),
                          block_size=block_size, chunk=chunk),
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis), P(axis)),
        out_specs=(P(axis), P()),
    )
    return fn(sig_u, sig_v, beta, seg)


# ---------------------------------------------------------------------------
# Mixed strategy driver
# ---------------------------------------------------------------------------

def recover_mixed(prepared, mesh, axis: str = "data",
                  block_size: int = 16, max_candidates: int = 128,
                  chunk: int = 2048, cutoff: int | None = None):
    """Full distributed recovery; returns status aligned with prepared order.

    Exactly equivalent to the serial oracle (property-tested): giant
    subtasks via the inner engine, the rest via LPT outer buckets.
    """
    prob = prepared.problem
    n_shards = int(np.prod([mesh.shape[a] for a in ([axis] if isinstance(axis, str) else axis)]))
    shard_of, giants, _ = partition_subtasks(
        prepared.subtask_sizes, n_shards, cutoff=cutoff)

    m = prob.m
    status_global = np.full(m, STATUS_SKIPPED, dtype=np.int8)
    seg_np = np.asarray(prob.seg)
    tracer = get_tracer()
    metrics = get_metrics()
    metrics.inc("dist.recoveries")
    with tracer.span("dist.recover_mixed", n_shards=n_shards,
                     giants=len(giants), m=m) as msp:
        # --- inner engine for each giant subtask, one at a time ---
        starts = np.flatnonzero(
            np.concatenate([[True], seg_np[1:] != seg_np[:-1]]))
        start_of = {int(seg_np[s]): int(s) for s in starts if seg_np[s] >= 0}
        inner_rounds = 0
        for sid in giants:
            st = start_of[sid]
            sz = int(prepared.subtask_sizes[sid])
            m_loc = int(np.ceil(sz / (n_shards * chunk))) * chunk
            m_tot = m_loc * n_shards
            sl = slice(st, st + sz)

            def pad(x):
                x = np.asarray(x[sl])
                out = np.full((m_tot,) + x.shape[1:],
                              pad_fill_value(x.dtype), dtype=x.dtype)
                out[:sz] = x
                return jnp.asarray(out)

            bs = max(block_size, 32)
            with tracer.span("dist.inner", subtask=int(sid), edges=sz,
                             m_tot=m_tot) as isp:
                status, rounds = recover_inner(
                    pad(np.asarray(prob.sig_u)), pad(np.asarray(prob.sig_v)),
                    pad(np.asarray(prob.beta)), pad(seg_np),
                    mesh, axis=axis, block_size=bs, chunk=chunk)
                status_global[sl] = np.asarray(status)[:sz]
                rounds = int(np.asarray(rounds).reshape(-1)[0])
                # per-round collective payload: one all_gather of the
                # candidate pack (two signature blocks + beta + rank) from
                # every shard — the engine's only communication
                c1 = int(np.asarray(prob.sig_u).shape[1])
                pack_bytes = n_shards * bs * (2 * c1 * 4 + 4 + 4)
                isp.set(rounds=rounds,
                        collective_bytes=rounds * pack_bytes)
                metrics.inc("dist.inner_rounds", rounds)
                metrics.inc("dist.collective_bytes", rounds * pack_bytes)
            inner_rounds += rounds

        # --- outer engine for everything else ---
        outer_rounds = 0
        if np.any(shard_of >= 0):
            with tracer.span("dist.outer", n_shards=n_shards) as osp:
                sharded = build_outer_shards(prob, prepared.subtask_sizes,
                                             shard_of, n_shards, chunk=chunk)
                status, rounds = recover_outer(
                    sharded, mesh, axis=axis, block_size=block_size,
                    max_candidates=max_candidates, chunk=chunk)
                status = np.asarray(status).reshape(-1)
                src = np.asarray(sharded.src_row).reshape(-1)
                ok = src >= 0
                status_global[src[ok]] = status[ok]
                outer_rounds = int(np.max(np.asarray(rounds))) if np.asarray(
                    rounds).size else 0
                osp.set(rounds=outer_rounds)
                metrics.inc("dist.outer_rounds", outer_rounds)
        msp.set(inner_rounds=inner_rounds, outer_rounds=outer_rounds)
    return status_global
