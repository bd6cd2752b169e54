"""Device microseconds per learner step on instructions scoped
``torso:attn_full``: the attention layer's blocked kernels (masked products,
softmax, their backward and the recomputed forward) with the padding to whole
blocks (``hybrid_times.py``)."""
import hybrid_times


def read(r):
    return hybrid_times.read(r, "attn_full")
