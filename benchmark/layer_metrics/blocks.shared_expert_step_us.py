"""Device microseconds per learner step on instructions scoped
``torso:shared_expert``: the shared expert's SwiGLU, forward, recomputation and backward
(``blocks_times.py``)."""
import blocks_times


def read(r):
    return blocks_times.read(r, "shared_expert")
