"""Counts XLA compiles through ``jax.monitoring`` (copied from
``chip_smoke.CompileMeter``, PR 23): backend compile requests, how many of
them the persistent cache served, and the seconds inside compile-or-load.
The window must see no request at all."""

from __future__ import annotations


class CompileMeter:
    def __init__(self):
        self.requests = 0      # backend compile requests, hit or miss
        self.cache_hits = 0    # served from the persistent cache
        self.seconds = 0.0     # wall inside compile-or-load
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snap(self):
        return (self.requests, self.cache_hits, self.seconds)
