"""Device microseconds per learner step on instructions scoped
``torso:dense_ffn``: every layer's SwiGLU with its norm (``hybrid_times.py``)."""
import hybrid_times


def read(r):
    return hybrid_times.read(r, "dense_ffn")
