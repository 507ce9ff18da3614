"""The benchmark's own host spans around its calls into the program.

Each span is kept in memory as (name, start, end) on ``time.perf_counter``,
and, while the profiler runs, also written into its trace as a
``TraceAnnotation`` named ``bench.<name>``, so the trace reduction can put
device idle gaps beside what the host was doing.
"""
from __future__ import annotations

import contextlib
import time

PREFIX = "bench."


class Spans:
    def __init__(self):
        self.records = []       # (name, start_s, end_s)
        self.annotate = False   # True while the profiler is recording

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(PREFIX + name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> list:
        return [b - a for n, a, b in self.records if n == name]
