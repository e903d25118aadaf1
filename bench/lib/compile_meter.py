"""Compilations and persistent-cache hits, counted from JAX's monitoring
events (a copy of the meter in the program's ``chip_smoke.py``)."""
from __future__ import annotations


class CompileMeter:
    """Counts backend compilations, their seconds, and persistent-cache
    hits (programs loaded instead of compiled), from the moment it is
    made.  ``snapshot()`` gives the three as a tuple."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.compiles, self.secs, self.hits
