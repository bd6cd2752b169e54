"""Device microseconds per learner step on instructions whose own metadata is
``stage:optimizer`` or ``stage:target_sync``: what is left of the optimizer's
pass over the parameters outside every other stage's fusions, and the hoisted
target-network sync.  Not the optimizer's whole cost: the compiler fuses each
layer's update into that layer's weight-gradient fusion, and an event is one
instruction, credited whole to the stage its own metadata names, so that part
reads under ``learner.backward_us_per_step`` (0.25 us here against 27 us at
B=512, my chip runs, PR 26).  The traced run prints the share of time in
fusions that hold several stages (13-37%): the forward / backward / optimizer
split is good to that share and no better."""
import stage_times


def read(r):
    return stage_times.read(r, "optimizer", "target_sync")
