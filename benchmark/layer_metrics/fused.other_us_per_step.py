"""Device microseconds per learner step of the fused program that no stage
metric reads: instructions with no stage (their consumers have none or
disagree), events the program's HLO text does not hold, and time inside a run
with no op running."""
import stage_times


def read(r):
    return stage_times.read_rest(r)
