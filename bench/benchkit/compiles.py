"""Counts XLA compilations while a window is open.

JAX reports every backend compile request (``BACKEND_COMPILE_EVENT``),
whether it compiled or loaded the executable from the persistent cache,
and each load separately (``/jax/compilation_cache/cache_hits``).  A
compile is a request that was not a load.
"""
from __future__ import annotations

import threading

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Register once per process; ``open()``/``close()`` bound a window."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._open = False
        self.requests = 0
        self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == BACKEND_COMPILE:
            with self._lock:
                if self._open:
                    self.requests += 1

    def _event(self, event, **kwargs):
        if event == CACHE_HIT:
            with self._lock:
                if self._open:
                    self.loads += 1

    def open(self):
        with self._lock:
            self._open, self.requests, self.loads = True, 0, 0

    def close(self):
        with self._lock:
            self._open = False

    @property
    def compiles(self) -> int:
        return self.requests - self.loads
