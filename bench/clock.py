"""Compile seconds and compile counts, from JAX's own monitoring events.

JAX reports the duration of every trace, lowering and backend compile
through `jax.monitoring`, and every persistent-cache hit as an event.
Listeners cannot be removed, so one CompileClock lives for the process.
"""
from __future__ import annotations

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax
        self.seconds = 0.0
        self.lowerings = 0
        self.backend_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in (TRACE, LOWER, BACKEND):
            self.seconds += duration
        if event == LOWER:
            self.lowerings += 1
        elif event == BACKEND:
            self.backend_compiles += 1

    def _event(self, event, **kw):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "lowerings": self.lowerings,
                "backend_compiles": self.backend_compiles,
                "cache_hits": self.cache_hits}

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in before}
