"""Device microseconds per learner step on instructions scoped
``torso:dense_ffn``: the leading dense layer's SwiGLU with its norm, forward, recomputation and backward
(``blocks_times.py``)."""
import blocks_times


def read(r):
    return blocks_times.read(r, "dense_ffn")
