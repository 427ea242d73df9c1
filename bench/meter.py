"""Compile counter and host spans for one benchmark process.

``CompileMeter`` is a copy of ``chip_smoke.CompileMeter``: JAX reports a
backend-compile duration for every program it builds, cache hits included
(then the seconds are the load), so programs actually compiled are
``programs - cache hits``.
"""
from __future__ import annotations

import contextlib
import time


class CompileMeter:
    """Counts XLA programs built, their seconds and compile-cache hits."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == self.BACKEND_COMPILE:
                self.programs += 1
                self.seconds += duration

        def on_event(event, **_):
            if event == self.CACHE_HIT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return (self.programs, self.seconds, self.cache_hits)

    def since(self, snap):
        p, s, h = snap
        programs, hits = self.programs - p, self.cache_hits - h
        return {
            "programs": programs,
            "compiled_programs": programs - hits,
            "compile_cache_hits": hits,
            "compile_s": self.seconds - s,
        }


class Spans:
    """Named host spans of the benchmark, kept in memory.

    Each span is also a ``jax.profiler.TraceAnnotation`` when ``annotate``
    is on, so a profiler trace of the window carries the benchmark's spans
    on the same clock as the device's operations.
    """

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records = []  # (name, start, end) on time.perf_counter()

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def begin(self, name: str):
        """Open a span closed by ``end`` (for spans that callbacks delimit)."""
        cm = self.span(name)
        cm.__enter__()
        return cm

    @staticmethod
    def end(cm):
        cm.__exit__(None, None, None)

    def durations(self, name: str):
        return [b - a for n, a, b in self.records if n == name]
