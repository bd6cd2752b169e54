"""Host spans around the benchmark's calls into each layer, kept in memory.
With ``annotate`` on they are also written into the profiler's trace (as
``bench:<name>``), on the device trace's clock."""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.rows: list = []   # (name, start, end) on time.perf_counter
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation("bench:" + name)
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))

    def total(self, name: str, t0: float = float("-inf"), t1: float = float("inf")) -> tuple:
        """(seconds, count) of the spans of that name that start in [t0, t1)."""
        rows = [r for r in self.rows if r[0] == name and t0 <= r[1] < t1]
        return sum(r[2] - r[1] for r in rows), len(rows)
