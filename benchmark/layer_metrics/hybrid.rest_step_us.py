"""Device microseconds per learner step outside the four parts read by name:
what of the fused program none of them claims (replay stages, stem, head,
loss, clip, optimizer, target sync, the loop's own time) plus the other
programs' time a step (``hybrid_times.py``).  With the four it adds up to
``fused.us_per_step`` plus the other programs' time a step."""
import hybrid_times


def read(r):
    return hybrid_times.read(r, "rest")
