"""Device microseconds per learner step on instructions scoped
``torso:mixer``: the attention layers' norm, projections, RoPE, head gate and ``W_o``, forward, recomputation and backward
(``blocks_times.py``)."""
import blocks_times


def read(r):
    return blocks_times.read(r, "mixer")
