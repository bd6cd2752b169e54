"""Device microseconds per learner step outside the seven parts read by name:
what of the fused program none of them claims (replay stages, stem, head,
loss, clip, optimizer, target sync, the loop's own time) plus the other
programs' time a step (``parts_times.py``).  With the seven it adds up to
``fused.us_per_step`` plus the other programs' time a step."""
import parts_times


def read(r):
    return parts_times.read(r, "rest")
