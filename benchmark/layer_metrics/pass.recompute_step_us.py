"""Device microseconds per learner step on ops under the pull-back whose
``op_name`` holds jax's ``rematted_computation`` or ``pass:again``: what a
``jax.checkpoint`` or a hand-written backward computes a second time; 0.0 where
the network recomputes nothing (``pass_times.py``)."""
import pass_times


def read(r):
    return pass_times.read(r, "recompute")
