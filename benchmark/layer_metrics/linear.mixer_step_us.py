"""Device microseconds per learner step on instructions scoped ``torso:mixer``
and no part inside it: norm, projections, convolutions, L2 norms, the two
low-rank gates, beta, the gated norm and the output projection of the
delta-rule layers; the softmax layer's projections and gate (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "mixer")
