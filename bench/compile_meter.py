"""The process's one ``jax.monitoring`` compile listener.

Counts backend compiles (persistent-cache loads included, as JAX reports
them), their seconds, and the persistent-cache hits.  JAX cannot unregister
a listener, so a process makes one meter (:func:`meter`) and reads it by
snapshots.
"""
from __future__ import annotations

import threading

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self) -> None:
        import jax
        self.totals = {"backend_compiles": 0, "cache_hits": 0,
                       "compile_s": 0.0}
        self._lock = threading.Lock()       # compiles run on many threads
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.totals["backend_compiles"] += 1
                self.totals["compile_s"] += secs

    def _event(self, event, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.totals["cache_hits"] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self.totals)


_meter = None
_meter_lock = threading.Lock()


def meter() -> CompileMeter:
    global _meter
    with _meter_lock:
        if _meter is None:
            _meter = CompileMeter()
        return _meter


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}
