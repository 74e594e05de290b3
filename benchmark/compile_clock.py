"""Seconds JAX spent obtaining executables (trace + lower + backend compile
or persistent-cache load) and persistent-cache hits and misses, read off
``jax.monitoring``.  Copied from ``chip_smoke.CompileClock`` (PR 21) so that
the yardstick lives with the benchmark."""
from __future__ import annotations


class CompileClock:
    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, secs, **_):
        if event in self._DURATIONS:
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.seconds, self.hits, self.misses)
