"""Device microseconds per learner step on ops scoped ``stage:sample``: the
inverse-CDF draw, the probabilities and the importance weights."""
import stage_times


def read(r):
    return stage_times.read(r, "sample")
