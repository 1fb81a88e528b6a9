"""Counts the executables JAX asks its backend for, from jax.monitoring.
The arithmetic is chip_smoke.py's `CompileLog` (PR 22), copied so that the
benchmark imports nothing of the smoke. The event spans the persistent-
cache lookup, so a cache hit counts as an executable made as much as a
compile does: inside a measured window the count must be 0."""
from __future__ import annotations

import jax


class CompileLog:
    def __init__(self):
        self.made = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.made += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
