"""Device microseconds per learner step on instructions scoped
``torso:shared_expert``: the shared expert's held columns (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "shared_expert")
