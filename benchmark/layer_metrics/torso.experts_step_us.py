"""Device microseconds per learner step on instructions scoped
``torso:experts``: the grouped products over the pair buffer and the gate between them, forward, recomputation and backward
(``torso_times.py``)."""
import torso_times


def read(r):
    return torso_times.read(r, "experts")
