"""Rates between call completions: whole calls over the real time they took.

A fused call is seconds long at some shapes, so "steps counted in a fixed
wall window" is quantised by one call.  Here the interval runs from the
completion of the last warm-up call to the completion of the first call that
finishes at or after ``seconds`` later; the rate is the work of the calls
completed inside it over its real length.  A stall inside the interval
lowers the rate by its real share; where the nominal window ends changes
nothing.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence


class Interval(NamedTuple):
    calls: int        # whole calls completed inside the interval
    elapsed_s: float  # its real length

    def rate(self, work_per_call: float) -> float:
        return self.calls * work_per_call / self.elapsed_s


def window_done(start: float, completions: Sequence[float], seconds: float) -> bool:
    """True once a call has completed at or after ``start + seconds``."""
    return bool(completions) and completions[-1] - start >= seconds


def call_boundary_interval(
    start: float, completions: Sequence[float], seconds: float
) -> Interval:
    """``start``: completion time of the last warm-up call.  ``completions``:
    completion times of the calls after it, in order.  The interval closes at
    the first completion at or after ``start + seconds``; completions after
    that are ignored."""
    if seconds <= 0:
        raise ValueError(f"seconds must be positive, got {seconds}")
    last = start
    for n, t in enumerate(completions, 1):
        if t < last:
            raise ValueError("completion times must not decrease")
        last = t
        if t - start >= seconds:
            return Interval(n, t - start)
    raise ValueError(
        f"no call completed at or after {seconds} s past the start: "
        f"{len(completions)} calls, the last at "
        f"{(completions[-1] - start) if completions else 0.0:.3f} s"
    )
