"""Registry of jit/Pallas hot entry points for the jaxpr auditor.

Each :class:`HotEntry` names one hot path, a builder that constructs the
callable plus two argument tuples — a *small* shape and a *sibling* shape
in the same RHS pow2 bucket (the service pads ``k`` to pow2 buckets, so
``k=5`` and ``k=7`` both land in bucket 8 and must lower to structurally
identical jaxprs for the warmup-per-bucket amortization to hold).

The entries mirror what production traffic actually traces:

* ``batched_pcg`` — ``make_solver``'s jitted end-to-end solve (PCG +
  V-cycle preconditioner), the service's single-device workhorse.
* ``vcycle_ref`` / ``vcycle_fused`` — the V-cycle closure alone in the
  jnp-reference and the Pallas-fused flavor (interpret mode: the audit
  runs on CPU; the traced structure is backend-independent).
* ``sharded_solver`` — the ``shard_map`` solve on a 1-device mesh (the
  smallest mesh that exercises the sharded code path).
* ``device_contraction`` — the jitted propose/accept hierarchy
  contraction kernel (static ``n``).
* ``harmonic_pcg`` — the Dirichlet-projected ``_pcg_loop`` under
  ``make_dirichlet_core``, the spectral plane's hot path.

Builders are lazy and memoized: the shared mesh2d hierarchy is built once
per process.  Everything here is float32 — the registry's
``declared_dtype`` is what the f64-promotion rule enforces (the f64
iterative-refinement wrapper lives *outside* these closures by design,
and that is exactly what the rule pins down).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class HotEntry:
    """One registered hot path.

    ``build()`` returns ``(fn, args_small, args_sibling, static_argnums)``;
    ``args_sibling`` is ``None`` when the RHS-bucket recompile check does
    not apply (e.g. the contraction has no RHS width).
    """

    name: str
    doc: str
    build: Callable[[], Tuple[Callable, tuple, Optional[tuple],
                              Tuple[int, ...]]]
    declared_dtype: str = "float32"


@functools.lru_cache(maxsize=1)
def _shared_artifacts():
    """(graph, idx, val, hierarchy) for the registry's suite graph —
    small enough to trace in seconds, deep enough for a real multilevel
    V-cycle (mesh2d 12x12 -> 2+ levels at coarse_n=16)."""
    from repro.core.graph import mesh2d
    from repro.solver.device_pcg import ell_laplacian
    from repro.solver.hierarchy import build_hierarchy

    g = mesh2d(12, 12, seed=0)
    idx, val = ell_laplacian(g)
    hier = build_hierarchy(g, coarse_n=16)
    return g, idx, val, hier


def _rhs(n: int, k: int):
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    b = rng.randn(n, k).astype(np.float32)
    b -= b.mean(axis=0, keepdims=True)
    return jnp.asarray(b)


def _build_batched_pcg():
    from repro.solver.device_pcg import make_solver
    g, idx, val, hier = _shared_artifacts()
    solve = make_solver(idx, val, hier, precond="hierarchy",
                        matvec_impl="ref")
    return solve, (_rhs(g.n, 5),), (_rhs(g.n, 7),), ()


def _build_vcycle(impl: str):
    from repro.solver.device_pcg import make_vcycle
    g, _, _, hier = _shared_artifacts()
    interpret = True if impl == "fused" else None
    vcycle = make_vcycle(hier, matvec_impl=impl, interpret=interpret)
    return vcycle, (_rhs(g.n, 5),), (_rhs(g.n, 7),), ()


def _build_sharded_solver():
    from repro.solver.sharded import make_sharded_solver
    g, idx, val, hier = _shared_artifacts()
    mesh = jax.make_mesh((1,), ("data",))
    solve = make_sharded_solver(idx, val, hier, precond="hierarchy",
                                mesh=mesh, matvec_impl="ref")
    return solve, (_rhs(g.n, 5),), (_rhs(g.n, 7),), ()


def _build_device_contraction():
    import jax.numpy as jnp
    from repro.solver.hierarchy import _device_contract_arrays
    g, _, _, _ = _shared_artifacts()
    args = (g.n, jnp.asarray(g.src), jnp.asarray(g.dst),
            jnp.asarray(g.weight))
    return _device_contract_arrays, args, None, (0,)


def _build_harmonic_pcg():
    import jax.numpy as jnp
    from repro.core.device_graph import DeviceGraph
    from repro.spectral.harmonic import make_dirichlet_core
    g, _, _, _ = _shared_artifacts()
    dg = DeviceGraph.from_graph(g)
    solve = make_dirichlet_core(dg)
    interior = jnp.asarray(
        (np.arange(g.n) >= g.n // 4).astype(np.float32))
    tol = jnp.float32(1e-5)
    maxiter = jnp.int32(50)
    return (solve, (interior, _rhs(g.n, 5), tol, maxiter),
            (interior, _rhs(g.n, 7), tol, maxiter), ())


HOT_ENTRIES: Tuple[HotEntry, ...] = (
    HotEntry("batched_pcg",
             "make_solver jit'd batched PCG + V-cycle (single device)",
             _build_batched_pcg),
    HotEntry("vcycle_ref",
             "make_vcycle closure, jnp reference matvec",
             lambda: _build_vcycle("ref")),
    HotEntry("vcycle_fused",
             "make_vcycle closure, Pallas-fused kernels (interpret)",
             lambda: _build_vcycle("fused")),
    HotEntry("sharded_solver",
             "make_sharded_solver shard_map solve on a 1-device mesh",
             _build_sharded_solver),
    HotEntry("device_contraction",
             "jit'd propose/accept hierarchy contraction (static n)",
             _build_device_contraction),
    HotEntry("harmonic_pcg",
             "make_dirichlet_core projected _pcg_loop (spectral plane)",
             _build_harmonic_pcg),
)
