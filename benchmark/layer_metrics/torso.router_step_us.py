"""Device microseconds per learner step on instructions scoped
``torso:router``: the expert layers' norm, scores, top-k, sort, dispatch into the pair buffer and combine, forward, recomputation and backward
(``torso_times.py``)."""
import torso_times


def read(r):
    return torso_times.read(r, "router")
