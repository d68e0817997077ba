"""Compile accounting, host spans and the wall clock of a run."""
from __future__ import annotations

import contextlib
import time


class CompileClock:
    """Seconds XLA spends compiling or fetching from the persistent cache,
    the number of such programs, and the cache hits, read from JAX's own
    monitoring events. ``window`` marks the measured window: any program
    compiled or fetched inside it is counted in ``in_window``."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        self.in_window = 0
        self.window = False
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.programs += 1
            if self.window:
                self.in_window += 1

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1


class Spans:
    """Named host spans. While a trace is taken they are written into the
    profiler's trace (``TraceAnnotation``), on the device's clock, so the
    reduction can say what the host was doing in each idle gap; otherwise
    they cost nothing."""

    def __init__(self):
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.tracing:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield


def now() -> float:
    return time.perf_counter()
