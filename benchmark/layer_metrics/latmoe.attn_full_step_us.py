"""Device microseconds per learner step on instructions scoped
``torso:attn_full``: the causal attention layer's blocked kernels, forward and backward (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "attn_full")
