"""Device microseconds per learner step on instructions scoped
``torso:dense_ffn``: the leading dense layers' SwiGLU with its norm, forward, recomputation and backward
(``torso_times.py``)."""
import torso_times


def read(r):
    return torso_times.read(r, "dense_ffn")
