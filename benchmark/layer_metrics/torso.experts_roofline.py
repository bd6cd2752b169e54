"""The grouped products' share of their roofline: the least time a step's
expert products can take for the pairs the run really routed to held experts
(``<ops_count>.expert_floor_s``: their FLOPs over the peak, or the reads of
the experts' weights) over the device time on instructions scoped
``torso:experts``."""
import importlib

import torso_times


def read(r):
    us, pairs = torso_times.read(r, "experts"), torso_times.held_pairs_per_step(r)
    if not us or pairs is None or "ops_count" not in r.config:
        return None
    ops = importlib.import_module(r.config["ops_count"])
    floor_s, _bound = ops.expert_floor_s(r.config, r.peaks, pairs)
    return floor_s / (us * 1e-6) * 100.0
