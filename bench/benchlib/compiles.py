"""Counts compilations from ``jax.monitoring`` events.

Every new program is lowered to MLIR once, whether XLA then compiles it or
loads it from the persistent cache, so lowerings inside the measured window
count compiles there (there should be none).
"""
from __future__ import annotations

import jax

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    def __init__(self):
        self.lowerings = 0
        self.backend_compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == LOWER:
            self.lowerings += 1
        elif event == COMPILE:
            self.backend_compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_):
        if event == HIT:
            self.cache_hits += 1
        elif event == MISS:
            self.cache_misses += 1

    def snapshot(self) -> tuple[int, int]:
        return self.lowerings, self.backend_compiles
