"""Device microseconds per learner step on instructions scoped
``torso:delta_scan``: the delta-rule layers' chunked scans (the cut, the running
sums and decays, the pair scores, the triangular solve, the products inside
and across chunks), forward, recomputation and backward (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "delta_scan")
