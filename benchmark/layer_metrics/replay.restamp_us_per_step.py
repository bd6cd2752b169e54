"""Device microseconds per learner step on ops scoped ``stage:restamp``: the
priorities from the TD errors and their write-back into the masses."""
import stage_times


def read(r):
    return stage_times.read(r, "restamp")
