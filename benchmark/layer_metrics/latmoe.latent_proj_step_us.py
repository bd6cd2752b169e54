"""Device microseconds per learner step on instructions scoped
``torso:latent_proj``: the expert layers' two latent projections, ``W_down`` and ``W_up``,
forward, recomputation and backward (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "latent_proj")
