"""Device microseconds per learner step on ops scoped ``stage:forward`` and not
under ``transpose(``: the three forward passes and the loss."""
import stage_times


def read(r):
    return stage_times.read(r, "forward")
