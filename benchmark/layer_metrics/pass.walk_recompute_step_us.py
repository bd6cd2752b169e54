"""Device microseconds per learner step on the instructions of a recurrent
walk (part ``ssm_scan`` or ``delta_scan``) whose pass is ``recompute``; 0.0
where the network has no such part (``pass_times.py``)."""
import pass_times


def read(r):
    return pass_times.read(r, "walk_recompute")
