"""Compile seconds, compiles and persistent-cache hits, from jax's own
monitoring events. Register once per process: the listeners stay."""

from __future__ import annotations

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, secs: float, **_) -> None:
        if event == _COMPILE:
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    @property
    def programs(self) -> int:
        """Programs compiled or loaded from the persistent cache so far."""
        return self.compiles + self.cache_hits
