"""Device microseconds per learner step on instructions scoped
``torso:delta_scan``: the delta-rule layers' chunked recurrences under the
bounded gate, forward, recomputation and backward (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "delta_scan")
