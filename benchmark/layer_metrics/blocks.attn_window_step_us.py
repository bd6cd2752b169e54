"""Device microseconds per learner step on instructions scoped
``torso:attn_window``: the sliding layers' blocked attention kernels (masked products, softmax, their backward and the recomputed forward) with the padding to whole blocks
(``blocks_times.py``)."""
import blocks_times


def read(r):
    return blocks_times.read(r, "attn_window")
