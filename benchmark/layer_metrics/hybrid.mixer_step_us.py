"""Device microseconds per learner step on instructions scoped ``torso:mixer``
and no part inside it: norm, input projection, convolution, gated norm and
output projection of the state-space layers, the attention layer's
projections (``hybrid_times.py``)."""
import hybrid_times


def read(r):
    return hybrid_times.read(r, "mixer")
