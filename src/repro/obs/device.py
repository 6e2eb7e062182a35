"""Device-timeline annotations: semantic labels for jit'd solver internals.

Host-side spans (:mod:`repro.obs.trace`) time dispatch, not device
execution — under jit the V-cycle is one opaque XLA computation.  Two
mechanisms put solver semantics back onto device timelines, and a third
puts JAX's compiles onto the host's:

  * :func:`named_scope` — ``jax.named_scope`` labels attach to the jaxpr /
    HLO **at trace time** (zero runtime cost, safe inside jit and
    ``shard_map``), so XLA profiles and HLO dumps show ``vcycle.L0.down``
    instead of anonymous fusions.  Always on.
  * :func:`trace_annotation` — ``jax.profiler.TraceAnnotation`` marks the
    host thread's dispatch window in the XLA profiler timeline; gated on
    the repro tracer being enabled so the disabled hot path stays free.
  * :func:`install_compile_listener` — a ``jax.monitoring`` listener that
    counts every backend compile (``jax.compiles``) and every executable
    loaded from the persistent cache (``jax.compile_cache_loads``) in
    :func:`~repro.obs.get_metrics`, and, while the tracer is enabled,
    records each compile as a ``jax.compile`` span on the compiling
    thread, nested under whatever program span is open there.

The first two degrade to ``contextlib.nullcontext`` when jax lacks the API
(or is absent entirely — this keeps :mod:`repro.obs` importable
everywhere); the listener is then not installed.
"""
from __future__ import annotations

import contextlib
import threading
import time

from repro.obs.metrics import get_metrics
from repro.obs.trace import get_tracer

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def named_scope(name: str):
    """Trace-time name scope for ops created under it (no runtime cost)."""
    try:
        import jax
        return jax.named_scope(name)
    except Exception:
        return contextlib.nullcontext()


def trace_annotation(name: str):
    """XLA-profiler host annotation around a dispatch; no-op unless the
    repro tracer is enabled."""
    if not get_tracer().enabled:
        return contextlib.nullcontext()
    try:
        import jax
        ta = getattr(jax.profiler, "TraceAnnotation", None)
        return ta(name) if ta is not None else contextlib.nullcontext()
    except Exception:
        return contextlib.nullcontext()


class annotated_span:
    """A tracer span and an XLA TraceAnnotation entered/exited together —
    the host span times the dispatch, the annotation labels the same window
    in the device profiler."""

    def __init__(self, name: str, **attrs):
        self._span = get_tracer().span(name, **attrs)
        self._anno = trace_annotation(name)

    def __enter__(self):
        self._anno.__enter__()
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        return self._anno.__exit__(*exc)


# per thread: whether a cache load fired since the thread's last compile
_compile_tls = threading.local()
_install_lock = threading.Lock()
_installed = False


def _on_duration(event, duration_secs, **kwargs):
    if event != BACKEND_COMPILE:
        return
    t1 = time.perf_counter_ns()
    dur = int(duration_secs * 1e9)
    # JAX loads from its cache only inside a compile request, on the
    # requesting thread, so a flag set since this thread's last request
    # belongs to this one
    hit = getattr(_compile_tls, "hit", False)
    _compile_tls.hit = False
    metrics = get_metrics()
    metrics.inc("jax.compiles")
    if hit:
        metrics.inc("jax.compile_cache_loads")
    get_tracer().complete("jax.compile", t1 - dur, dur,
                          fun=kwargs.get("fun_name", ""), cache_hit=hit)


def _on_event(event, **kwargs):
    if event == CACHE_HIT:
        _compile_tls.hit = True


def install_compile_listener() -> bool:
    """Register the compile listener with ``jax.monitoring``, once per
    process (later calls do nothing).  A compile request served from the
    persistent cache counts in both ``jax.compiles`` and
    ``jax.compile_cache_loads`` and carries ``cache_hit=True``; real
    compiles are the difference.  Returns whether the listener is in."""
    global _installed
    with _install_lock:
        if not _installed:
            try:
                import jax
                jax.monitoring.register_event_duration_secs_listener(
                    _on_duration)
                jax.monitoring.register_event_listener(_on_event)
            except (ImportError, AttributeError):
                return False
            _installed = True
    return True
