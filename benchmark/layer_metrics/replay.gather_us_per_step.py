"""Device microseconds per learner step on ops scoped ``stage:gather``: the row
fetches from the ring, and the layout copies of the ring that only they consume."""
import stage_times


def read(r):
    return stage_times.read(r, "gather")
