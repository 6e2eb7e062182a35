"""The staged sparsification pipeline: one object, three pluggable stages.

    from repro.pipeline import Pipeline, pdgrass_config, fegrass_config

    pipe = Pipeline(pdgrass_config(alpha=0.05))
    sparsifier = pipe.run(graph)

    # feGRASS is the same harness with a different recovery stage:
    base = Pipeline(fegrass_config(alpha=0.05)).run(graph)

``prepare`` runs the shared steps 1-3 (tree stage, binary lifting, score
stage, subtask grouping) and returns a :class:`repro.core.sparsify.Prepared`
that any engine can consume — comparing engines on identical inputs (the
paper's apples-to-apples protocol) is ``run(g, prepared=shared_prep)``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lifting as lift_mod
from repro.core import recovery as rec_mod
from repro.core.graph import Graph
from repro.core.sparsify import Prepared, Sparsifier
from repro.obs import get_metrics, get_tracer
from repro.pipeline.config import PipelineConfig, validate
from repro.pipeline.stages import RECOVERY_ENGINES, SCORE_STAGES, TREE_STAGES


class Pipeline:
    """A configured sparsification pipeline; stateless apart from its config."""

    def __init__(self, config: Optional[PipelineConfig] = None):
        self.config = validate(config if config is not None
                               else PipelineConfig())

    def __repr__(self) -> str:
        c = self.config
        return (f"Pipeline(tree={c.tree.kind!r}, score={c.score.kind!r}, "
                f"recovery={c.recovery.kind!r}, alpha={c.alpha})")

    # -- steps 1-3: tree, lifting, scores, subtask grouping ------------------

    def prepare(self, graph: Graph) -> Prepared:
        """Everything up to (and excluding) edge recovery — engine-agnostic."""
        cfg = self.config
        n, c, chunk = graph.n, cfg.c, cfg.chunk
        tracer = get_tracer()
        with tracer.span("pipeline.prepare", n=n, m=graph.m) as psp:
            src = jnp.asarray(graph.src)
            dst = jnp.asarray(graph.dst)
            w = jnp.asarray(graph.weight)

            with tracer.span("pipeline.tree", kind=cfg.tree.kind):
                tree = TREE_STAGES[cfg.tree.kind](n, src, dst, w, cfg.tree)
                if tracer.enabled:      # end the span on the tree itself
                    jax.block_until_ready(tree)
            with tracer.span("pipeline.lifting"):
                lift = lift_mod.build_lifting(n, tree.parent, tree.parent_w,
                                              tree.depth)

            in_tree = np.asarray(tree.in_tree)
            off_ids = np.flatnonzero(~in_tree)
            ou = jnp.asarray(graph.src[off_ids])
            ov = jnp.asarray(graph.dst[off_ids])
            ow = jnp.asarray(graph.weight[off_ids])

            with tracer.span("pipeline.scores", kind=cfg.score.kind,
                             m_off=int(off_ids.shape[0])):
                l = lift_mod.lca(lift, ou, ov)
                r_t = lift_mod.resistance_distance(lift, ou, ov, l)
                score = SCORE_STAGES[cfg.score.kind](
                    ow, r_t, cfg.score,
                    # runtime ctx for solver-backed stages (er_exact): the
                    # host graph, tree membership, off-tree endpoints
                    graph=graph, in_tree=in_tree,
                    u=graph.src[off_ids], v=graph.dst[off_ids])

                depth = lift.depth
                beta = jnp.minimum(
                    jnp.minimum(depth[ou] - depth[l], depth[ov] - depth[l]), c
                ).astype(jnp.int32)

                sig = lift_mod.ancestor_signatures(tree.parent, c)
                sig_u = sig[ou]
                sig_v = sig[ov]

            with tracer.span("pipeline.grouping"):
                # Host-side ordering: LCA ascending, score descending
                # (stable).
                l_np = np.asarray(l)
                score_np = np.asarray(score)
                order = np.lexsort((-score_np, l_np))
                l_sorted = l_np[order]
                if len(l_sorted):
                    seg_change = np.concatenate(
                        [[True], l_sorted[1:] != l_sorted[:-1]])
                    seg_ids = np.cumsum(seg_change) - 1
                    n_subtasks = int(seg_ids[-1]) + 1
                else:  # graph is a tree — no off-tree edges, no subtasks
                    seg_ids = np.zeros(0, dtype=np.int64)
                    n_subtasks = 0
                sizes = np.bincount(seg_ids, minlength=max(n_subtasks, 1))

                m_off = off_ids.shape[0]
                m_pad = max(chunk, int(math.ceil(m_off / chunk)) * chunk)
                pad = m_pad - m_off

                def pad_rows(x, fill, reorder=True):
                    x = np.asarray(x)
                    if reorder:
                        x = x[order]
                    if pad:
                        shape = (pad,) + x.shape[1:]
                        x = np.concatenate(
                            [x, np.full(shape, fill, dtype=x.dtype)])
                    return jnp.asarray(x)

                problem = rec_mod.RecoveryProblem(
                    sig_u=pad_rows(sig_u, -1),
                    sig_v=pad_rows(sig_v, -1),
                    beta=pad_rows(beta, -1),
                    # seg_ids already in sorted order (built from l_sorted)
                    seg=pad_rows(seg_ids.astype(np.int32), -1,
                                 reorder=False),
                    score=pad_rows(score_np, -np.inf),
                )
            psp.set(n_subtasks=n_subtasks, m_off=int(m_off))
        get_metrics().inc("pipeline.prepares")
        return Prepared(
            graph=graph, tree=tree, lift=lift,
            off_edge_id=off_ids[order],
            problem=problem, n_subtasks=n_subtasks,
            subtask_sizes=sizes,
        )

    # -- step 4: recovery through the configured engine ----------------------

    def run(self, graph: Graph, prepared: Optional[Prepared] = None,
            **ctx) -> Sparsifier:
        """Full pipeline -> :class:`Sparsifier`.

        ``prepared`` reuses shared steps 1-3 across configs/engines; ``ctx``
        forwards runtime-only objects to the engine (e.g. ``mesh=...`` for
        the distributed engine).
        """
        cfg = self.config
        prep = prepared if prepared is not None else self.prepare(graph)
        target = min(int(math.ceil(cfg.alpha * graph.n)), prep.m_off)

        engine = RECOVERY_ENGINES[cfg.recovery.kind]
        with get_tracer().span("pipeline.recovery", kind=cfg.recovery.kind,
                               target=target) as rsp:
            recovered_mask, engine_stats = engine(prep, target, cfg, **ctx)
            rsp.set(n_recovered=int(recovered_mask.sum()))
        m = get_metrics()
        m.inc("pipeline.runs")
        m.inc(f"pipeline.engine.{cfg.recovery.kind}")

        stats = dict(engine_stats)
        # Strict-similarity engines complete in one pass (the paper's claim);
        # the multipass engine reports its own pass count.
        stats.setdefault("passes", 1)
        stats.update(
            n_recovered=int(recovered_mask.sum()),
            target=target,
            n_subtasks=prep.n_subtasks,
            max_subtask=int(prep.subtask_sizes.max()) if prep.n_subtasks
            else 0,
        )
        return Sparsifier(graph=graph,
                          tree_mask=np.asarray(prep.tree.in_tree),
                          recovered_mask=recovered_mask, stats=stats)


def run_pipeline(graph: Graph, config: Optional[PipelineConfig] = None,
                 **ctx) -> Sparsifier:
    """One-shot convenience: ``Pipeline(config).run(graph, **ctx)``."""
    return Pipeline(config).run(graph, **ctx)
