"""Compile seconds and persistent-cache hits, from JAX's own monitoring
events (a cache hit is recorded as a short compile). Lets a run split its
set-up and count compilations inside the measured window, which should be
none."""

from __future__ import annotations


class CompileClock:
    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.compiles, self.hits

    def since(self, mark):
        return {"compile_s": self.seconds - mark[0], "compiles": self.compiles - mark[1],
                "cache_hits": self.hits - mark[2]}
