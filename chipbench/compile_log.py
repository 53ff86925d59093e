"""What JAX itself reports about compilation (``jax.monitoring`` events):
every program it builds — a persistent-cache hit included — with its
seconds, and the persistent cache's hits and misses. A miss is recorded
only for a program that took long enough to be written to the cache (one
second by default), which is the count a warm start is held to."""
from __future__ import annotations


class CompileLog:
    KEYS = ("programs", "compile_s", "cache_hits", "cache_misses")

    def __init__(self):
        from jax import monitoring
        self.programs, self.compile_s = 0, 0.0
        self.cache_hits, self.cache_misses = 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {k: getattr(self, k) for k in self.KEYS}

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in self.KEYS}
