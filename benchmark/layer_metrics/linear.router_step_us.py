"""Device microseconds per learner step on instructions scoped
``torso:router``: the expert layers' norm, scores, top-k, sort, gathers and
scatter-adds around the grouped products (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "router")
