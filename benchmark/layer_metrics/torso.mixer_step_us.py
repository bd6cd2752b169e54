"""Device microseconds per learner step on instructions scoped
``torso:mixer``: short convolutions and attention with their norms, forward, recomputation and backward
(``torso_times.py``)."""
import torso_times


def read(r):
    return torso_times.read(r, "mixer")
