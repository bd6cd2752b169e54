"""Tracing/profiling subsystem (SURVEY §5: absent in the reference).

Three tools, smallest-first:

  * ``StageTimer`` — per-component wall-clock accumulators for the host-side
    pipeline stages (sample / place / step / write-back / ingest).  The
    north-star metrics are throughputs, so per-stage µs/step is the first
    derivative every perf investigation needs; the async runtime exports
    these in its JSONL metrics.
  * ``trace(logdir)`` — context manager around ``jax.profiler`` device
    tracing (TensorBoard-viewable).  A profiler that cannot start raises:
    a run asked to trace either produces a trace or fails.
  * ``subtractive_timing`` / ``slope_timing`` — time K-step fused program
    *variants* with stages deleted; the difference isolates each stage's
    device cost.  Used by ``tools/profile_fused.py``.

The reference has no profiling at all (``time`` is imported in its
learner.py:3 solely for ``sleep`` — reference SURVEY §5).
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict
from typing import Callable, Dict, Iterator


class StageTimer:
    """Named wall-clock accumulators: ``with timer.stage("sample"): ...``.

    Cheap enough for hot loops (one ``perf_counter`` pair per section plus
    one uncontended lock acquire — the ``+=`` on a dict item is a
    read-modify-write, NOT atomic under CPython, so cross-thread updates
    need the lock to not lose counts).
    """

    def __init__(self):
        self._total_s: Dict[str, float] = defaultdict(float)
        self._count: Dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self._total_s[name] += dt
                self._count[name] += 1

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._total_s[name] += seconds
            self._count[name] += 1

    def us_per_call(self) -> Dict[str, float]:
        with self._lock:  # readers too: a concurrent first-use of a stage
            # name inserts into the defaultdict mid-iteration otherwise
            totals, counts = dict(self._total_s), dict(self._count)
        return {
            name: round(totals[name] / max(1, counts[name]) * 1e6, 1)
            for name in totals
        }

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            totals, counts = dict(self._total_s), dict(self._count)
        return {
            name: {
                "total_s": round(totals[name], 4),
                "calls": counts[name],
                "us_per_call": round(
                    totals[name] / max(1, counts[name]) * 1e6, 1
                ),
            }
            for name in totals
        }

    def reset(self) -> None:
        with self._lock:
            self._total_s.clear()
            self._count.clear()


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """``jax.profiler`` device trace into ``logdir`` (TensorBoard format).

    Raises whatever ``start_trace`` / ``stop_trace`` raise: a caller that
    asked for a trace must not get a green run and no trace."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def subtractive_timing(
    variants: Dict[str, Callable[[], None]],
    force: Callable[[], None],
    warmup: int = 2,
    repeats: int = 3,
) -> Dict[str, float]:
    """Time each no-arg variant (already closed over its inputs), forcing
    completion via ``force`` (a host read of a value that depends on every
    call).

    Returns {name: seconds} of the best (min) of ``repeats`` runs — min is
    the right estimator for device work measured through a noisy host.

    Each force includes one host round trip: fine for multi-second
    workloads, too coarse for µs-scale ones; use ``slope_timing`` for those.
    """
    out: Dict[str, float] = {}
    for name, fn in variants.items():
        for _ in range(warmup):
            fn()
        force()
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            force()
            best = min(best, time.perf_counter() - t0)
        out[name] = best
    return out


def slope_timing(
    variants: Dict[str, Callable[[], None]],
    force: Callable[[], None],
    n_small: int = 2,
    n_big: int = 10,
    repeats: int = 3,
) -> Dict[str, float]:
    """Marginal per-call device time via a two-point linear fit.

    Wall time of n chained calls followed by one force is
    T(n) ≈ fixed + n·device, where ``fixed`` is whatever the sync itself
    costs — the slope (T(n_big) − T(n_small)) / (n_big − n_small) cancels
    it and measures per-call device time.  Calls must be chained (each
    consuming the previous call's outputs) so the device can't overlap them.

    Returns {name: seconds per call}, min over ``repeats`` slope estimates.
    """
    out: Dict[str, float] = {}
    for name, fn in variants.items():
        fn()
        force()  # compile + steady state
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(n_small):
                fn()
            force()
            t1 = time.perf_counter()
            for _ in range(n_big):
                fn()
            force()
            t2 = time.perf_counter()
            slope = ((t2 - t1) - (t1 - t0)) / (n_big - n_small)
            best = min(best, slope)
        out[name] = max(best, 0.0)
    return out
