"""Device microseconds per learner step on instructions scoped
``torso:router``: scores, the choice of groups and of experts, the sort, the
walk's gathers and scatters (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "router")
