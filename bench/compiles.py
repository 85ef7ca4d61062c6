"""Compiles and persistent-cache reads, counted through ``jax.monitoring``."""
from __future__ import annotations


class CompileCounter:
    """XLA backend compiles, their seconds, and persistent-cache hits."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def install(self) -> "CompileCounter":
        import jax

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def total(self) -> int:
        """Compiles plus persistent-cache reads so far."""
        return self.compiles + self.cache_hits
