"""Device microseconds per learner step on the instructions of a recurrent
walk (part ``ssm_scan`` or ``delta_scan``) whose pass is ``backward``: a
walk's forward share is its part's ``*_step_us`` less this and
``pass.walk_recompute_step_us`` (``pass_times.py``)."""
import pass_times


def read(r):
    return pass_times.read(r, "walk_backward")
