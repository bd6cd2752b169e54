"""Device microseconds per learner step on instructions scoped
``torso:experts``: the held experts' grouped products in the latent, forward,
recomputation and backward (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "experts")
