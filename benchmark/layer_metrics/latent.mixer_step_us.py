"""Device microseconds per learner step on instructions scoped ``torso:mixer``
and no narrower part: norms, projections, convolutions, gates, RoPE, the
latent's norm and expansion, ``W_o`` (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "mixer")
