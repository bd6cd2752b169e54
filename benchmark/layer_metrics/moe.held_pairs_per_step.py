"""Token-expert pairs routed to held experts a learner step, summed over the
step's three forwards and the expert layers: the fused call's metrics
(``StepMetrics.routing``), averaged over the window's calls."""


def read(r):
    return r.counters.get("held_pairs_per_step")
