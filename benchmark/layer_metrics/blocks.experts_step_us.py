"""Device microseconds per learner step on instructions scoped
``torso:experts``: the grouped products over the held pairs and the gate between them, forward, recomputation and backward
(``blocks_times.py``)."""
import blocks_times


def read(r):
    return blocks_times.read(r, "experts")
