"""Compile activity of the process, read from ``jax.monitoring``.

The listener counts every backend compile and every persistent-cache
hit, wherever it happens (the mapping service compiles on its worker
thread), so a run can count the compiles that fell inside its window.
"""

from __future__ import annotations

import threading

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileEvents:
    """Counts of backend compiles and of persistent-cache hits."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.backend_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def _on_duration(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.backend_compiles += 1

    def compiles(self) -> int:
        """Executables built or loaded: backend compiles plus persistent
        cache hits (a hit stands in for a backend compile)."""
        with self._lock:
            return self.backend_compiles + self.cache_hits
