"""Counts backend compiles and persistent-cache hits from JAX's monitoring
events: a compile request that the persistent cache served is a hit, any
other is a compile."""
from __future__ import annotations


class CompileCounter:
    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits

    def since(self, snap) -> dict:
        req, hits = self.requests - snap[0], self.hits - snap[1]
        return {"compiles": req - hits, "cache_hits": hits}
