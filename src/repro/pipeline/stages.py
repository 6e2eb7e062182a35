"""Stage registries: named, pluggable implementations for each pipeline stage.

Three registries, looked up by the ``kind`` strings in
:mod:`repro.pipeline.config`:

  * ``TREE_STAGES``      — ``(n, src, dst, weight, TreeConfig) -> TreeResult``
  * ``SCORE_STAGES``     — ``(w_off, r_tree, ScoreConfig, **ctx) ->
                             score [m_off]``
  * ``RECOVERY_ENGINES`` — ``(prep, target, PipelineConfig, **ctx) ->
                             (recovered_mask [graph.m] bool, stats dict)``

Registering a new stage is one decorated function — the GRASS family
(GRASS, feGRASS, pdGRASS, SF-GRASS) is a grid of (scoring rule x tree
strategy x recovery engine), and every cell is a config, not a fork.
``ctx`` carries runtime-only objects that don't belong in a serializable
config (the device ``mesh`` for the distributed engine; for score stages,
the host ``graph``, the tree membership mask, and the off-tree endpoints
``u``/``v`` that ``er_exact`` solves against).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import recovery as rec_mod
from repro.core import spanning_tree as st_mod
from repro.pipeline.config import PipelineConfig, ScoreConfig, TreeConfig

TREE_STAGES: dict = {}
SCORE_STAGES: dict = {}
RECOVERY_ENGINES: dict = {}


def register(registry: dict, name: str):
    def deco(fn):
        registry[name] = fn
        return fn
    return deco


# ---------------------------------------------------------------------------
# Tree stages (paper step 1)
# ---------------------------------------------------------------------------

@register(TREE_STAGES, "low_stretch")
def tree_low_stretch(n, src, dst, weight, cfg: TreeConfig):
    """feGRASS Definition 1: max-ST over effective weights (low-stretch)."""
    return st_mod.build_spanning_tree(n, src, dst, weight,
                                      mode="low_stretch")


@register(TREE_STAGES, "boruvka")
def tree_boruvka(n, src, dst, weight, cfg: TreeConfig):
    """Plain maximum-weight spanning tree (Boruvka on the raw weights)."""
    return st_mod.build_spanning_tree(n, src, dst, weight, mode="boruvka")


# ---------------------------------------------------------------------------
# Score stages (paper step 2: spectral criticality ordering)
# ---------------------------------------------------------------------------

@register(SCORE_STAGES, "w_times_r")
def score_w_times_r(w, r_t, cfg: ScoreConfig, **_):
    """Spectral criticality w(e) * R_T(e) — the feGRASS/pdGRASS default."""
    return w * r_t


@register(SCORE_STAGES, "r")
def score_r(w, r_t, cfg: ScoreConfig, **_):
    """Raw tree resistance distance (ignores the edge weight)."""
    return r_t


@register(SCORE_STAGES, "er_sample")
def score_er_sample(w, r_t, cfg: ScoreConfig, **_):
    """Effective-resistance sampling order (Spielman-Srivastava style).

    Gumbel-top-k: ranking by ``log(w * R_T) + Gumbel(seed)`` and keeping the
    top ``target`` draws a sample *without replacement* with inclusion
    probability proportional to w(e) * R_T(e) — the leverage-score proxy —
    instead of the deterministic top scores.  Deterministic per seed.
    """
    key = jax.random.PRNGKey(cfg.seed)
    gumbel = jax.random.gumbel(key, w.shape, dtype=w.dtype)
    return jnp.log(jnp.maximum(w * r_t, 1e-30)) + gumbel


@register(SCORE_STAGES, "er_exact")
def score_er_exact(w, r_t, cfg: ScoreConfig, *, graph=None, in_tree=None,
                   u=None, v=None, **_):
    """True leverage scores w(e) * R_G(e) from batched Laplacian solves.

    Replaces the tree-resistance proxy ``R_T`` (an upper bound that can
    badly over-rank edges shortcut elsewhere) with the exact effective
    resistance of the *full* graph, computed on the spanning-tree-
    preconditioned solver — the ground truth ``er_sample`` approximates.
    ``cfg.tol`` is the per-column solve tolerance.
    """
    if graph is None:
        raise ValueError("er_exact needs graph context (graph, in_tree, "
                         "u, v) from the pipeline; bare calls only get "
                         "the tree proxy")
    # Late import: pipeline <- spectral <- solver <- pipeline would cycle
    # at module load; by call time every module is initialized.
    from repro.spectral.resistance import exact_offtree_resistances

    r = exact_offtree_resistances(graph, in_tree, u, v, tol=cfg.tol)
    return w * jnp.asarray(r, dtype=w.dtype)


# ---------------------------------------------------------------------------
# Recovery engines (paper step 4)
# ---------------------------------------------------------------------------

def mask_from_status(prep, status, target) -> np.ndarray:
    """Top-``target`` recovered rows by score -> [graph.m] bool edge mask."""
    keep = np.asarray(rec_mod.select_top(
        jnp.asarray(status), prep.problem.score, target))
    keep = keep[: prep.m_off]
    mask = np.zeros(prep.graph.m, dtype=bool)
    mask[prep.off_edge_id[keep]] = True
    return mask


@register(RECOVERY_ENGINES, "rounds")
def engine_rounds(prep, target, cfg: PipelineConfig, **ctx):
    """The JAX round engine (strict similarity, single logical pass)."""
    r = cfg.recovery
    status, stats = rec_mod.recover_rounds(
        prep.problem, jnp.int32(target),
        block_size=r.block_size, max_candidates=r.max_candidates,
        stop_at_target=r.stop_at_target, chunk=cfg.chunk)
    # one designated sync for all three counters instead of three
    # sequential blocking scalarizations
    rounds, candidates, killed = jax.device_get(
        (stats.rounds, stats.candidates, stats.killed_in_block))
    return mask_from_status(prep, status, target), {
        "rounds": int(rounds),
        "candidates": int(candidates),
        "killed_in_block": int(killed),
    }


@register(RECOVERY_ENGINES, "serial")
def engine_serial(prep, target, cfg: PipelineConfig, **ctx):
    """The numpy oracle — the paper's sequential per-subtask greedy."""
    status = rec_mod.recover_serial(prep.problem)
    return mask_from_status(prep, status, target), {"rounds": -1}


@register(RECOVERY_ENGINES, "distributed")
def engine_distributed(prep, target, cfg: PipelineConfig, mesh=None, **ctx):
    """The mixed outer/inner mesh engine from :mod:`repro.core.distributed`.

    ``mesh`` comes through the runtime context (``Pipeline.run(..., mesh=m)``);
    without one, a 1-axis mesh over all local devices is built.
    """
    from repro.core import distributed as dist_mod

    r = cfg.recovery
    if mesh is None:
        mesh = jax.make_mesh((jax.device_count(),), (r.axis,))
    status = dist_mod.recover_mixed(
        prep, mesh, axis=r.axis, block_size=r.block_size,
        max_candidates=r.max_candidates, chunk=cfg.chunk, cutoff=r.cutoff)
    return mask_from_status(prep, status, target), {
        "rounds": -1, "n_shards": int(mesh.shape[r.axis])}


@register(RECOVERY_ENGINES, "multipass")
def engine_multipass(prep, target, cfg: PipelineConfig, **ctx):
    """feGRASS recovery: loose (vertex-cover) similarity, multi-pass, host.

    This is the baseline the paper measures against (its Table II); running
    it under the same ``Pipeline`` harness makes pdGRASS-vs-feGRASS a pure
    recovery-stage diff.
    """
    from repro.core.fegrass import loose_multipass_recover

    return loose_multipass_recover(prep, target, c=cfg.c,
                                   max_passes=cfg.recovery.max_passes)
