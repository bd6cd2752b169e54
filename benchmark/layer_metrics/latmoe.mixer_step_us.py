"""Device microseconds per learner step on instructions scoped
``torso:mixer``
and no narrower part: the blocks' first norms, the Mamba-2 layers' projections,
convolutions, gate and group norm, the attention layer's four projections (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "mixer")
