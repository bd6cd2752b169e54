"""Device microseconds per learner step on instructions scoped
``torso:attn_full``: the softmax layer's blocked kernels (masked products,
softmax, their backward and the recomputed forward) with the padding to whole
blocks (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "attn_full")
