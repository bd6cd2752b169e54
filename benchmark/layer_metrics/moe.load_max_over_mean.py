"""The largest load of a held expert over the mean load of the held experts,
each summed over a step's forwards and expert layers (the fused call's
metrics), over the window's calls: 1 is an even split."""


def read(r):
    top, mean = r.counters.get("load_max_per_step"), r.counters.get("load_mean_per_step")
    return None if top is None or not mean else top / mean
