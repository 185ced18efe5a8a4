"""Compile accounting from ``jax.monitoring`` events.

A copy of ``chip_smoke.CompileClock`` (which the benchmark may not
import), extended with a count of executables fetched: every
backend-compile event is one program that was not yet in memory, whether
the persistent cache then held it or not.
"""

from __future__ import annotations

import threading

_DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds spent tracing, lowering and compiling; executables fetched
    (``programs``); persistent-cache hits.  Counts since creation; take
    differences of :meth:`read` around a region."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._seconds = 0.0
        self._programs = 0
        self._hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _DURATIONS:
            with self._lock:
                self._seconds += duration
                if event == _BACKEND:
                    self._programs += 1

    def _event(self, event, **_):
        if event == _HIT:
            with self._lock:
                self._hits += 1

    def read(self) -> dict:
        with self._lock:
            return {"seconds": self._seconds, "programs": self._programs,
                    "cache_hits": self._hits}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
