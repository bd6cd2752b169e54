"""Device microseconds per learner step on instructions scoped
``torso:dense_ffn``: the leading dense layer's SwiGLU (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "dense_ffn")
