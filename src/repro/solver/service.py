"""Request/response Laplacian solve engine with slot batching.

The serving counterpart of ``serve/engine.py`` for the pdGRASS pipeline,
v2 request plane (handles / tickets / per-request configs):

    svc = SolverService(pipeline=pdgrass_config(alpha=0.05))
    h = svc.register(g)                       # content hash paid ONCE
    t0 = svc.submit(SolveRequest(graph=h, b=b0))
    t1 = svc.submit(SolveRequest(graph=h, b=b1,
                                 pipeline=fegrass_config(alpha=0.05)))
    svc.flush()                               # one flush, two groups
    x0, x1 = t0.result().x, t1.result().x     # resolvable in any order

The scheduler groups pending requests by ``(graph_fingerprint,
config_fingerprint)``: all right-hand sides of a group stack into one
``[n, k]`` batch served by a single jit'd device PCG against that group's
cached hierarchy, so pdGRASS- and feGRASS-preconditioned requests for the
same mesh coexist in one flush and each hit the right artifacts.
``warmup(handle, configs=[...])`` prefetches artifacts + solver closures
ahead of traffic; ``stats()`` snapshots the cache, store, scheduler, and
per-config solve counters.

RHS batches are padded to the next power of two, and to at least two
columns, so the jit cache sees a handful of shapes instead of one per
request count (the slot idiom of the LM engine: fixed slots, variable
occupancy); see :func:`_call_width`.

v1 compatibility: ``submit``/``solve`` still accept raw ``Graph``s (they
are registered on the fly), tickets subclass ``int`` so ``flush()[ticket]``
indexing keeps working, and ticket ids are service-wide monotonic — stable
across flushes instead of per-flush list positions.
"""
from __future__ import annotations

import collections
import copy
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph
from repro.obs import Metrics, get_metrics, get_tracer
from repro.obs.device import install_compile_listener, trace_annotation
from repro.pipeline import PipelineConfig, pdgrass_config
from repro.pipeline import validate as validate_config
from repro.solver import cache as cache_mod
from repro.solver.cache import LRUCache, artifact_key, mesh_descriptor
from repro.solver.device_pcg import (default_matvec_impl, ell_laplacian,
                                     make_solver)
from repro.solver.hierarchy import build_hierarchy
from repro.solver.requests import (AdmissionError, GraphHandle, GraphStore,
                                   SolveRequest, SolveResponse, SolveTicket)

# artifact schema tag: bump on layout changes
# v5: device-resident hierarchy contraction (propose/accept matching) +
#     Chebyshev-smoothed V-cycle; the contraction mode joins the key extras
# v6: mesh-sharded solve plane — the mesh descriptor (axis name, shard
#     count; None when single-device) joins the key extras, and
#     contraction="sharded" is a distinct mode.  v5 on-disk entries miss
#     cleanly and rebuild.
# v7: Pallas-fused V-cycle — ``matvec_impl`` ("fused" / "kernel" / "ref")
#     joins the key extras so fused- and unfused-built artifacts never
#     alias even though the hierarchy arrays are identical today (the key
#     must cover everything that shaped the cached value, and future fused
#     builds may bake kernel-specific layouts).  v6 on-disk entries miss
#     cleanly and rebuild.
_SCHEMA = "solver-v7"


# Narrowest device call.  On a TPU v5e a width-1 solve costs about 3.3x
# the per-iteration time of any width from 2 to 32, so a lone right-hand
# side runs as a width-2 call with one inert padding column.
_MIN_WIDTH = 2


def _call_width(k: int) -> int:
    """Columns of the device call that solves ``k`` right-hand sides: the
    next power of two, at least ``_MIN_WIDTH``.  Flushes stack to it and
    ``warmup`` compiles it, so the two always agree."""
    p = _MIN_WIDTH
    while p < k:
        p *= 2
    return p


class SolverService:
    """Cached, batched sparsifier-preconditioned Laplacian solver."""

    def __init__(self, alpha: Optional[float] = None,
                 precond: str = "hierarchy",
                 coarse_n: int = 64, cache_capacity: int = 16,
                 disk_dir: Optional[str] = None,
                 disk_max_entries: Optional[int] = None,
                 disk_max_bytes: Optional[int] = None,
                 matvec_impl: Optional[str] = None, tile_n: int = 256,
                 max_refine: int = 3,
                 pipeline: Optional[PipelineConfig] = None,
                 store: Optional[GraphStore] = None,
                 store_max_entries: Optional[int] = None,
                 store_max_bytes: Optional[int] = None,
                 contraction: Optional[str] = None,
                 max_pending_columns: Optional[int] = None,
                 mesh=None, shard_axis: str = "data",
                 metrics: Optional[Metrics] = None,
                 interpret: Optional[bool] = None):
        """``pipeline`` selects the default sparsification pipeline backing
        the preconditioner (any family member — pdGRASS, feGRASS, custom
        stage mixes); individual requests may override it with
        ``SolveRequest(pipeline=...)``.  When omitted, a pdGRASS config is
        built from ``alpha`` (default 0.05).  Passing both is a conflict:
        alpha lives inside the config.  ``store`` shares a
        :class:`GraphStore` between services;
        ``store_max_entries``/``store_max_bytes`` cap the default store's
        persisted ``graphstore/`` tier (mtime-LRU eviction, mirroring the
        artifact ``disk_max_*`` caps) and are a conflict with an explicit
        ``store`` — caps live on the store you build.

        ``contraction`` selects the hierarchy-build matching path
        (``"device"`` propose/accept rounds, ``"host"`` sequential oracle,
        or ``"sharded"`` mesh-distributed rounds); it participates in the
        artifact fingerprint, so the modes never share cache entries.
        ``max_pending_columns`` bounds the scheduler: a ``submit`` that
        would push the queued RHS column count past the budget raises
        :class:`AdmissionError` instead of growing the next flush without
        limit (``None`` = unbounded).

        ``mesh`` switches the whole solve plane onto a device mesh: the
        hierarchy build contracts with mesh-sharded propose/accept rounds
        (``contraction`` defaults to ``"sharded"``), and the batched PCG +
        V-cycle run row-sharded under ``shard_map`` over ``shard_axis``
        (see :mod:`repro.solver.sharded`).  The mesh descriptor joins the
        artifact cache key (schema v6), so single-device and sharded
        artifacts never alias.

        ``matvec_impl`` selects the solve plane's kernel path — ``"fused"``
        (Pallas-fused V-cycle: batched spmv + fused Chebyshev + fused
        restrict+residual), ``"kernel"`` (per-column Pallas spmv), or
        ``"ref"`` (jnp composition); ``None`` takes
        :func:`~repro.solver.device_pcg.default_matvec_impl` ("ref" on
        every backend: Mosaic refuses the Pallas kernels today).  The
        impl joins the artifact key (schema v7).  ``interpret`` forces
        Pallas interpret/compiled mode for all kernels this service builds;
        ``None`` resolves from the backend (see
        :func:`repro.kernels.ops.resolve_interpret`)."""
        if pipeline is not None and alpha is not None:
            raise ValueError(
                "pass either alpha or pipeline, not both — alpha is "
                "pipeline.alpha (use pipeline.replace(alpha=...))")
        if contraction is None:
            contraction = "sharded" if mesh is not None else "device"
        if contraction not in ("device", "host", "sharded"):
            raise ValueError(
                f"unknown contraction mode {contraction!r}; "
                f"want 'device', 'host' or 'sharded'")
        if contraction == "sharded" and mesh is None:
            raise ValueError("contraction='sharded' needs a mesh")
        if mesh is not None and precond == "jacobi":
            # fail at construction, not first flush: the sharded plane
            # supports 'hierarchy' and 'none' (jacobi is a single-device
            # comparison baseline)
            raise NotImplementedError(
                "precond='jacobi' is not supported with mesh= — "
                "use precond='hierarchy' or 'none'")
        self.pipeline = (pipeline if pipeline is not None
                         else pdgrass_config(
                             alpha=0.05 if alpha is None else alpha,
                             chunk=512))
        self.alpha = self.pipeline.alpha
        self.precond = precond
        self.coarse_n = coarse_n
        self.contraction = contraction
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.max_refine = max_refine
        self.max_pending_columns = max_pending_columns
        self.matvec_impl = matvec_impl or default_matvec_impl()
        self.tile_n = tile_n
        self.interpret = interpret
        # With a disk tier configured, the default store persists beside it
        # (``<disk_dir>/graphstore/<fingerprint>.npz``): a restarted service
        # rehydrates its handles AND hits the persisted artifacts — no
        # caller re-registers edge arrays, no O(m) re-fingerprints.
        if store is None:
            store = GraphStore(
                persist_dir=(os.path.join(disk_dir, "graphstore")
                             if disk_dir else None),
                max_entries=store_max_entries, max_bytes=store_max_bytes)
        elif store_max_entries is not None or store_max_bytes is not None:
            raise ValueError(
                "store_max_entries/store_max_bytes configure the default "
                "store — with an explicit store=, set the caps on it "
                "(GraphStore(max_entries=..., max_bytes=...))")
        self.store = store
        # Per-service metrics registry (``solver.*`` / ``cache.*``
        # namespaces): two services never share counters, so fresh-service
        # stats start from zero.  Module-level instrumentation (pipeline,
        # hierarchy, distributed) lands in the process-wide registry and is
        # merged into ``stats()["metrics"]`` read-only.
        self.metrics = metrics if metrics is not None else Metrics()
        install_compile_listener()    # jax.compile spans and counters
        self.cache = LRUCache(capacity=cache_capacity, disk_dir=disk_dir,
                              disk_max_entries=disk_max_entries,
                              disk_max_bytes=disk_max_bytes,
                              metrics=self.metrics)
        # fingerprint -> jit'd solve closure, LRU-bounded (see _solver_for)
        self._solvers: "collections.OrderedDict[str, object]" = \
            collections.OrderedDict()
        # [(ticket, handle, request)] — the scheduler's input queue.
        # Guarded by _lock: submits may race the daemon's background
        # flusher (and each other) once a SolverDaemon wraps this service.
        self._pending: List[Tuple[SolveTicket, GraphHandle, SolveRequest]] = []
        self._pending_columns = 0
        self._next_ticket = 0
        # Canonical shared-state inventory, machine-checked by
        # repro.analysis.lock_lint: every field below may only be touched
        # inside `with self._lock` or from a *_locked method.
        # lock: self._lock
        #   _pending _pending_columns _next_ticket _sched
        #   _solvers _warmed _timing _conv_digests _solves_by_config
        self._lock = threading.RLock()
        # "submitted" counts admitted requests (rejected ones never enter
        # the queue), so submitted/rejected is the admission split.
        self._sched = {"submitted": 0, "flushes": 0, "groups": 0,
                       "requests_solved": 0, "group_failures": 0,
                       "rejected": 0}
        self._warmed: set = set()   # (key, k_pad) buckets warmup has run
        self._solves_by_config: "collections.Counter[str]" = \
            collections.Counter()
        # cumulative compile-vs-solve wall-time split (ms), see stats()
        self._timing = {"warmup_compile_ms": 0.0, "setup_ms": 0.0,
                        "solve_ms": 0.0}
        # config digests with convergence histograms (see stats())
        self._conv_digests: set = set()

    # -- graph plane ---------------------------------------------------------

    def register(self, graph: Union[Graph, GraphHandle]) -> GraphHandle:
        """Register a graph with the service's store; the returned handle
        carries the memoized content fingerprint, so requests built from it
        never re-hash the edge arrays."""
        return self.store.register(graph)

    # -- artifact plane ------------------------------------------------------

    def _config_for(self, request: SolveRequest) -> PipelineConfig:
        return request.pipeline if request.pipeline is not None \
            else self.pipeline

    def _key(self, handle: GraphHandle, config: PipelineConfig) -> str:
        return artifact_key(handle.fingerprint, config, extra=(
            _SCHEMA, self.precond, self.coarse_n, self.contraction,
            self.matvec_impl,
            mesh_descriptor(self.mesh, self.shard_axis)))

    def artifacts(self, graph: Union[Graph, GraphHandle],
                  key: Optional[str] = None,
                  pipeline: Optional[PipelineConfig] = None):
        """(idx, val, hierarchy), source — cached pipeline steps 1-4 and the
        multilevel chain, keyed by (graph content, PipelineConfig, precond).

        ``pipeline`` defaults to the service-wide config; ``key`` lets the
        scheduler skip recomputing the group key it already holds."""
        handle = self.store.register(graph)
        config = pipeline if pipeline is not None else self.pipeline
        if key is None:
            key = self._key(handle, config)

        def build():
            g = handle.graph
            idx, val = ell_laplacian(g)
            hier = (build_hierarchy(g, config=config, coarse_n=self.coarse_n,
                                    contraction=self.contraction,
                                    mesh=self.mesh,
                                    shard_axis=self.shard_axis)
                    if self.precond == "hierarchy" else None)
            return idx, val, hier

        value, source = self.cache.get_or_build(key, build)
        return key, value, source

    def _solver_for(self, key: str, artifacts):
        """jit'd solve closures are process-local (not picklable), so they
        live beside — not inside — the artifact cache, LRU-bounded to the
        same capacity (each closure retains device arrays + executables)."""
        with self._lock:
            fn = self._solvers.get(key)
            if fn is not None:
                self._solvers.move_to_end(key)
                return fn
        # build OUTSIDE the lock: make_solver stages device arrays and can
        # take a while — holding _lock here would stall every submit
        idx, val, hier = artifacts
        fn = make_solver(idx, val, hierarchy=hier, precond=self.precond,
                         matvec_impl=self.matvec_impl, tile_n=self.tile_n,
                         mesh=self.mesh, shard_axis=self.shard_axis,
                         interpret=self.interpret)
        with self._lock:
            # two racing builders: first insert wins, both get one closure
            fn = self._solvers.setdefault(key, fn)
            self._solvers.move_to_end(key)
            while len(self._solvers) > self.cache.capacity:
                self._solvers.popitem(last=False)
            return fn

    def warmup(self, graph: Union[Graph, GraphHandle],
               configs: Optional[Sequence[PipelineConfig]] = None,
               widths: Optional[Sequence[int]] = None) -> Dict[str, str]:
        """Prefetch artifacts + solver closures for ``graph`` under each
        config (default: the service-wide one) ahead of traffic.  Returns
        ``{config_digest: artifact_source}`` — "miss" means built now,
        "mem"/"disk" mean the cache already held it.

        ``widths`` additionally jit-warms the solve itself: for every
        requested RHS width the slot bucket a flush pads it to
        (:func:`_call_width`: a power of two, at least two) runs one
        zero-RHS solve (a zero column converges in zero iterations, so
        the cost is pure XLA compilation), moving compile time out of the
        first real flush.  The cumulative compile wall time lands in
        ``stats()["timing"]["warmup_compile_ms"]`` — compare against
        ``timing["solve_ms"]`` for the compile-vs-solve split."""
        handle = self.register(graph)
        sources: Dict[str, str] = {}
        if widths is not None and any(int(w) < 1 for w in widths):
            raise ValueError(f"widths must be >= 1, got {list(widths)}")
        buckets = sorted({_call_width(int(w)) for w in (widths or ())})
        tracer = get_tracer()
        for config in (configs if configs is not None else [self.pipeline]):
            validate_config(config)
            key = self._key(handle, config)
            with tracer.span("solver.warmup", config=config.digest(),
                             buckets=buckets):
                _, artifacts, source = self.artifacts(handle, key=key,
                                                      pipeline=config)
                solve = self._solver_for(key, artifacts)
            sources[config.digest()] = source
            for k_pad in buckets:
                # Mirror the flush call signature exactly ([n, k_pad] f32
                # rhs, [k_pad] f32 tol, [k_pad] int32 maxiter) so the jit
                # cache entry compiled here is the one traffic hits.
                size_before = (solve._cache_size()
                               if hasattr(solve, "_cache_size") else None)
                t0 = time.perf_counter()
                res = solve(
                    jnp.zeros((handle.n, k_pad), jnp.float32),
                    tol=jnp.full((k_pad,), 1e-5, jnp.float32),
                    maxiter=jnp.full((k_pad,), 1, jnp.int32))
                jax.block_until_ready(res.x)
                # Book the wall time as compile only when this bucket
                # actually compiled — a re-warmed (or traffic-compiled)
                # bucket is a jit cache hit and must not inflate the split.
                # Without jit cache introspection (older jax), fall back to
                # first-warmup-per-bucket accounting (traffic-compiled
                # buckets may then book once; re-warms never double-count).
                compile_ms = (time.perf_counter() - t0) * 1e3
                with self._lock:
                    compiled = (solve._cache_size() > size_before
                                if size_before is not None
                                else (key, k_pad) not in self._warmed)
                    self._warmed.add((key, k_pad))
                    if compiled:
                        self._timing["warmup_compile_ms"] += compile_ms
                if compiled:
                    self.metrics.observe("solver.warmup.compile_ms",
                                         compile_ms)
                    self.metrics.inc("solver.warmup.compiles")
        return sources

    # -- request plane -------------------------------------------------------

    @staticmethod
    def _validate(request: SolveRequest) -> None:
        g = request.graph.graph if isinstance(request.graph, GraphHandle) \
            else request.graph
        b = np.asarray(request.b)
        if b.ndim not in (1, 2) or b.shape[0] != g.n:
            raise ValueError(
                f"rhs shape {b.shape} does not match graph with "
                f"{g.n} vertices (want [n] or [n, k])")
        # Validate in the f32 dtype the device solve actually runs in: this
        # catches NaN/inf in the input AND f64 magnitudes that overflow to
        # inf on the cast (both would silently poison the PCG iteration and
        # read back as non-convergence).
        with np.errstate(over="ignore"):
            finite = np.isfinite(b.astype(np.float32, copy=False)
                                 if b.dtype != np.float32 else b)
        if not finite.all():
            bad = int(b.size - finite.sum())
            raise ValueError(
                f"rhs contains {bad} value(s) that are non-finite in the "
                f"f32 solve precision (NaN/inf, or magnitude > f32 max) — "
                f"clean or rescale the rhs before submitting")
        if request.pipeline is not None:
            if not isinstance(request.pipeline, PipelineConfig):
                raise TypeError(
                    f"request.pipeline wants a PipelineConfig, got "
                    f"{type(request.pipeline).__name__}")
            validate_config(request.pipeline)
        if request.deadline_ms is not None and not request.deadline_ms > 0:
            raise ValueError(
                f"deadline_ms must be positive, got {request.deadline_ms}")

    def submit(self, request: SolveRequest) -> SolveTicket:
        """Queue a request; returns a :class:`SolveTicket` future resolved
        by the next flush() (or by ``ticket.result()``, which flushes).

        With ``max_pending_columns`` set, a submit whose RHS columns would
        push the queue past the budget raises :class:`AdmissionError`
        (counted in ``stats()["scheduler"]["rejected"]``) — backpressure
        instead of an unbounded flush."""
        self._validate(request)
        shape = np.shape(request.b)   # no copy — b may be device-resident
        cols = 1 if len(shape) == 1 else int(shape[1])
        handle = self.store.register(request.graph)
        with self._lock:
            if (self.max_pending_columns is not None
                    and self._pending_columns + cols
                    > self.max_pending_columns):
                self._sched["rejected"] += 1
                self.metrics.inc("solver.rejected")
                raise AdmissionError(self._pending_columns, cols,
                                     self.max_pending_columns)
            ticket = SolveTicket(self._next_ticket, service=self,
                                 request=request)
            self._next_ticket += 1
            self._sched["submitted"] += 1
            self._pending.append((ticket, handle, request))
            self._pending_columns += cols
        self.metrics.inc("solver.submitted")
        return ticket

    def _new_ticket(self, request: SolveRequest,
                    handle: Optional[GraphHandle] = None,
    ) -> Tuple[SolveTicket, GraphHandle]:
        """Validate + register + allocate a service-wide ticket id WITHOUT
        queueing: the entry point for external schedulers (the async daemon
        keeps its own fairness-ordered queue and hands batches straight to
        :meth:`_solve_batch`).  The ticket carries no service back-ref, so
        ``result()`` never triggers a caller-thread flush."""
        self._validate(request)
        if handle is None:
            handle = self.store.register(request.graph)
        with self._lock:
            ticket = SolveTicket(self._next_ticket, service=None,
                                 request=request)
            self._next_ticket += 1
        return ticket, handle

    def _has_pending(self, ticket: SolveTicket) -> bool:
        """Identity membership in the pending queue (``result()`` uses this
        to distinguish a flushable ticket from a stale/foreign one)."""
        with self._lock:
            return any(t is ticket for t, _, _ in self._pending)

    def flush(self) -> Dict[SolveTicket, SolveResponse]:
        """Solve everything pending — one batched PCG per distinct
        (graph, pipeline-config) group."""
        with self._lock:
            pending, self._pending = self._pending, []
            self._pending_columns = 0
            self._sched["flushes"] += 1
        self.metrics.inc("solver.flushes")
        with get_tracer().span("solver.flush", requests=len(pending)):
            return self._solve_batch(pending)

    def solve(self, graph: Union[Graph, GraphHandle], b: np.ndarray,
              tol: float = 1e-5, maxiter: int = 2000,
              pipeline: Optional[PipelineConfig] = None) -> SolveResponse:
        """Convenience single-request path.  Does NOT touch the pending
        queue — other submitted tickets stay queued for the next flush()."""
        req = SolveRequest(graph=graph, b=b, tol=tol, maxiter=maxiter,
                           pipeline=pipeline)
        ticket, handle = self._new_ticket(req)
        out = self._solve_batch([(ticket, handle, req)])
        if ticket not in out:      # single group: surface its failure
            raise ticket.error()
        return out[ticket]

    def stats(self) -> dict:
        """Snapshot of the serving planes: artifact cache (+ disk tier),
        graph store, scheduler counters, and per-config solve counts
        (keyed by ``PipelineConfig.digest()``).  ``store.hash_events``
        counts the O(m) content hashes this service's store triggered
        (``process_hash_events`` is the process-wide total) — traffic over
        registered graphs keeps both flat.

        Telemetry keys (see README "Observability"):

        * ``"metrics"`` — the flat namespaced registry: this service's
          ``solver.*`` / ``cache.*`` instruments merged over the
          process-wide ``pipeline.*`` / ``hierarchy.*`` / ``dist.*`` /
          ``store.hash_events`` ones (the namespaces are disjoint, so the
          merge never shadows).
        * ``"convergence"`` — per config digest: PCG iteration-count and
          final-relative-residual histograms plus setup/solve latency
          percentiles, observed once per flush group.

        The returned dict is a **deep copy**: callers may mutate it freely
        (diffing, annotating, json round-trips) without corrupting the
        service's live counters."""
        with self._lock:
            digests = sorted(self._conv_digests)
        convergence = {}
        for d in digests:
            convergence[d] = {
                "iters": self.metrics.histogram(
                    f"solver.pcg.iters.{d}").snapshot(),
                "relres": self.metrics.histogram(
                    f"solver.pcg.relres.{d}").snapshot(),
                "setup_ms": self.metrics.histogram(
                    f"solver.latency.setup_ms.{d}").snapshot(),
                "solve_ms": self.metrics.histogram(
                    f"solver.latency.solve_ms.{d}").snapshot(),
            }
        with self._lock:
            return copy.deepcopy({
                "cache": self.cache.stats,
                "store": {**self.store.stats,
                          "process_hash_events": cache_mod.HASH_EVENTS},
                "scheduler": {**self._sched, "pending": len(self._pending),
                              "pending_columns": self._pending_columns,
                              "max_pending_columns": self.max_pending_columns},
                "solves_by_config": dict(self._solves_by_config),
                "solvers": {"jit_closures": len(self._solvers),
                            "capacity": self.cache.capacity},
                "hierarchy": {"contraction": self.contraction,
                              "precond": self.precond},
                "mesh": {"descriptor": mesh_descriptor(self.mesh,
                                                       self.shard_axis)},
                "timing": dict(self._timing),
                "metrics": {**get_metrics().snapshot(),
                            **self.metrics.snapshot()},
                "convergence": convergence,
            })

    # -- scheduler -----------------------------------------------------------

    def _solve_batch(
        self, pending: List[Tuple[SolveTicket, GraphHandle, SolveRequest]],
    ) -> Dict[SolveTicket, SolveResponse]:
        groups: Dict[Tuple[str, str], List[int]] = {}
        keys: Dict[Tuple[str, str], str] = {}
        for i, (_, handle, req) in enumerate(pending):
            config = self._config_for(req)
            gid = (handle.fingerprint, config.fingerprint())
            if gid not in keys:
                keys[gid] = self._key(handle, config)
            groups.setdefault(gid, []).append(i)
        with self._lock:
            self._sched["groups"] += len(groups)
        self.metrics.inc("solver.groups", len(groups))

        # Groups fail independently: an exception while building or solving
        # one (graph, config) group fails only that group's tickets (their
        # result() re-raises it) — every other group still solves and
        # resolves.  A serving flush must never lose unrelated tickets.
        out: Dict[SolveTicket, SolveResponse] = {}
        for gid, members in groups.items():
            entries = [pending[i] for i in members]
            config = self._config_for(entries[0][2])
            try:
                solved = self._solve_group(entries, config, keys[gid])
            except Exception as e:
                with self._lock:
                    self._sched["group_failures"] += 1
                self.metrics.inc("solver.group_failures")
                for ticket, _, _ in entries:
                    ticket._fail(e)
                continue
            with self._lock:
                self._sched["requests_solved"] += len(entries)
                self._solves_by_config[config.digest()] += len(entries)
            self.metrics.inc("solver.requests_solved", len(entries))
            out.update(solved)
        return out

    def _solve_group(
        self, entries: List[Tuple[SolveTicket, GraphHandle, SolveRequest]],
        config: PipelineConfig, key: str,
    ) -> Dict[SolveTicket, SolveResponse]:
        """Build/fetch one (graph, config) group's artifacts and run its
        slot-batched solve, resolving every ticket in the group."""
        handle = entries[0][1]
        g = handle.graph
        config_digest = config.digest()
        tracer = get_tracer()
        with tracer.span("solver.group", config=config_digest,
                         n=g.n, requests=len(entries)) as group_span:
            return self._solve_group_inner(
                entries, config, key, g, config_digest, tracer, group_span)

    def _solve_group_inner(self, entries, config, key, g, config_digest,
                           tracer, group_span):
        """Body of :meth:`_solve_group`, factored out so the whole group
        nests under one ``solver.group`` span, whose children name every
        phase in order: ``solver.artifacts``, ``solver.stack``,
        ``solver.solve``, ``solver.residual``, then ``solver.refine`` and
        ``solver.residual`` per refinement pass, and ``solver.resolve``.
        ``solver.solve`` and ``solver.refine`` each hold one device call,
        from the copy of its right-hand side to the read-back of its
        answer, and carry ``pass``, ``k``, ``k_pad`` and ``loops`` (the
        call's while-loop trips)."""
        handle = entries[0][1]
        if tracer.enabled:
            group_span.set(tickets=[int(t) for t, _, _ in entries])
        with tracer.span("solver.artifacts", config=config_digest) as asp:
            t0 = time.perf_counter()
            _, artifacts, source = self.artifacts(handle, key=key,
                                                  pipeline=config)
            setup_ms = (time.perf_counter() - t0) * 1e3
            solve = self._solver_for(key, artifacts)
            asp.set(source=source)

        with tracer.span("solver.stack"):
            cols, owner = [], []   # owner[j] = (entry-idx, col-in-request)
            for e, (_, _, req) in enumerate(entries):
                b = np.asarray(req.b, dtype=np.float32)
                b = b[:, None] if b.ndim == 1 else b
                for j in range(b.shape[1]):
                    cols.append(b[:, j])
                    owner.append((e, j))
            k = len(cols)
            k_pad = _call_width(k)
            if k < _MIN_WIDTH:
                self.metrics.inc("solver.width_floor_groups")
            B = np.zeros((g.n, k_pad), np.float32)
            B[:, :k] = np.stack(cols, axis=1)
            # L is singular with nullspace = constants: only the mean-zero
            # component of b is solvable.  Center here so the residual
            # measurement below targets the solvable system (else the
            # unsolvable mean would read as non-convergence).
            B -= B.mean(axis=0)
            # Per-column tolerance and iteration budget: each request keeps
            # its own contract even when batched with stricter/larger
            # neighbors.  Padding columns are inert BY CONSTRUCTION —
            # tol=inf and maxiter=0 mean they can never drive batched_pcg's
            # while-loop (done from iteration zero) nor the refinement pass
            # (zero remaining budget, relres 0 <= inf), independent of the
            # separate zero-RHS short-circuit.
            reqs = [req for _, _, req in entries]
            tol_col = np.full(k_pad, np.inf)
            maxiter_col = np.zeros(k_pad, np.int32)
            for j, (e, _) in enumerate(owner):
                tol_col[j] = reqs[e].tol
                maxiter_col[j] = reqs[e].maxiter
            # The f32 device solve floors around 1e-7 relative residual;
            # ask it only for what it can deliver and let the f64
            # refinement passes close the rest.  Per column: a loose-tol
            # request batched with a strict one stops at its own contract
            # instead of riding along to the group minimum.
            inner_tol = np.maximum(tol_col, 1e-5).astype(np.float32)

        t0 = time.perf_counter()
        with tracer.span("solver.solve", **{"pass": 0}, k=k, k_pad=k_pad,
                         n=g.n) as sp, trace_annotation("solver.solve"):
            res = solve(jnp.asarray(B), tol=jnp.asarray(inner_tol),
                        maxiter=jnp.asarray(maxiter_col))
            x = np.asarray(res.x, dtype=np.float64)
            iters = np.asarray(res.iters).copy()
            if tracer.enabled:
                sp.set(loops=int(iters.max()))

        # Mixed-precision iterative refinement: the f32 device solve hits
        # its attainable-accuracy floor on large/ill-conditioned graphs,
        # so measure the true residual in f64 on the host and re-solve
        # for the correction on the device until tol is genuinely met.
        # The residual matvec runs over the Graph's own CSR arrays
        # (numpy f64, no scipy on the solve path).
        with tracer.span("solver.residual", **{"pass": 0}):
            B64 = B.astype(np.float64)
            bn = np.maximum(np.linalg.norm(B64, axis=0),
                            np.finfo(np.float64).tiny)
            resid = B64 - g.laplacian_matvec(x)
            relres = np.linalg.norm(resid, axis=0) / bn
            rhs = self._correction(resid, relres, tol_col, 0)
        refinements = 0
        while rhs is not None:
            rc, corr_tol = rhs
            refinements += 1
            # corrections draw from each column's remaining budget
            with tracer.span("solver.refine", **{"pass": refinements}, k=k,
                             k_pad=k_pad) as sp, \
                    trace_annotation("solver.refine"):
                corr = solve(jnp.asarray(rc), tol=jnp.asarray(corr_tol),
                             maxiter=jnp.asarray(np.maximum(
                                 maxiter_col - iters, 0)))
                dx = np.asarray(corr.x, dtype=np.float64)
                corr_iters = np.asarray(corr.iters)
                if tracer.enabled:
                    sp.set(loops=int(corr_iters.max()))
            with tracer.span("solver.residual", **{"pass": refinements}):
                x_new = x + dx
                resid_new = B64 - g.laplacian_matvec(x_new)
                relres_new = np.linalg.norm(resid_new, axis=0) / bn
                # accept per column whenever the correction improved it ...
                take = relres_new < relres
                x = np.where(take, x_new, x)
                resid = np.where(take, resid_new, resid)
                halved = np.any(relres_new < 0.5 * relres)
                relres = np.where(take, relres_new, relres)
                iters = iters + corr_iters
                # ... but stop once passes stall at the f32 floor
                rhs = (self._correction(resid, relres, tol_col, refinements)
                       if halved else None)
        solve_ms = (time.perf_counter() - t0) * 1e3

        with tracer.span("solver.resolve"):
            with self._lock:
                self._timing["setup_ms"] += setup_ms
                self._timing["solve_ms"] += solve_ms
                self._conv_digests.add(config_digest)
            conv = relres <= tol_col
            # Convergence telemetry, fetched ONCE per flush group from arrays
            # this path already materializes (iters/relres came back with the
            # solution — no extra device round-trip).  Padding columns are
            # excluded: only the k real right-hand sides count.
            m = self.metrics
            m.observe_many(f"solver.pcg.iters.{config_digest}",
                           np.asarray(iters[:k], dtype=np.float64))
            m.observe_many(f"solver.pcg.relres.{config_digest}",
                           np.asarray(relres[:k], dtype=np.float64))
            m.observe(f"solver.latency.setup_ms.{config_digest}", setup_ms)
            m.observe(f"solver.latency.solve_ms.{config_digest}", solve_ms)
            m.inc("solver.refinement_passes", refinements)
            if not bool(conv[:k].all()):
                m.inc("solver.unconverged_columns",
                      int(k - int(conv[:k].sum())))
            group_span.set(k=k, k_pad=k_pad, source=source,
                           refinements=refinements,
                           max_iters=int(np.max(iters[:k])) if k else 0,
                           converged=bool(conv[:k].all()))
            out: Dict[SolveTicket, SolveResponse] = {}
            for e, (ticket, _, req) in enumerate(entries):
                mine = [j for j, (ee, _) in enumerate(owner) if ee == e]
                xs = x[:, mine]
                if np.asarray(req.b).ndim == 1:
                    xs = xs[:, 0]
                response = SolveResponse(
                    x=xs, iters=iters[mine], relres=relres[mine],
                    converged=bool(conv[mine].all()), cache=source,
                    refinements=refinements, setup_ms=setup_ms,
                    solve_ms=solve_ms, config=config_digest)
                ticket._resolve(response)
                out[ticket] = response
            return out

    def _correction(self, resid, relres, tol_col, refinements):
        """The next refinement pass's right-hand side and per-column tol
        (both f32, as the device solve takes them), or ``None`` when no
        pass is due: the budget of passes is spent or every column meets
        its tol."""
        if refinements >= self.max_refine or not np.any(relres > tol_col):
            return None
        rc = resid - resid.mean(axis=0)
        # A correction need only close each column's gap to its tol:
        # solved to tol / relres relative to ``rc`` it leaves a true
        # residual of about tol (the loop aims at half its target).
        # Asking it for tol itself would solve to ~tol^2 and cost
        # about as many iterations as the first pass.
        corr_tol = np.clip(
            tol_col / np.maximum(relres, np.finfo(np.float64).tiny),
            1e-5, 1.0)
        return rc.astype(np.float32), corr_tol.astype(np.float32)
