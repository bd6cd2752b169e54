"""Device microseconds per learner step on ops under
``transpose(jvp(stage:forward))``: the backward pass, and under ``shard_map``
the gradient all-reduce, whose metadata puts it there."""
import stage_times


def read(r):
    return stage_times.read(r, "backward")
