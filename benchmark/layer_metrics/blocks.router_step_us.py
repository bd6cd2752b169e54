"""Device microseconds per learner step on instructions scoped
``torso:router``: the expert layers' norm, scores, top-k, sort, the walk's row gathers and combine, forward, recomputation and backward
(``blocks_times.py``)."""
import blocks_times


def read(r):
    return blocks_times.read(r, "router")
