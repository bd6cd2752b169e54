"""Device microseconds per learner step on the rest under
``transpose(jvp(stage:forward))``: cotangents, collectives, the optimizer's
update where it is fused into a weight gradient (``pass_times.py``)."""
import pass_times


def read(r):
    return pass_times.read(r, "backward")
