"""Device microseconds per learner step on instructions scoped
``torso:router``: the expert layers' norm, scores, the choice of 22 of 512, the sort, the
walk's gathers and scatters (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "router")
