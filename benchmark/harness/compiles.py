"""Counts backend compilations and when they happened, from JAX's own
monitoring events, so a run can show that none fell inside its window."""

from __future__ import annotations

import time


class CompileLog:
    def __init__(self):
        from jax import monitoring

        self.events = []  # (t_end perf_counter, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._ev)

    def _dur(self, event, seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), float(seconds)))

    def _ev(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def inside(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)

    def summary(self) -> dict:
        return {"compiles": len(self.events),
                "compile_seconds": sum(s for _, s in self.events),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
