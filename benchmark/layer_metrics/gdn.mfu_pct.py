"""The whole step's share of the peak with the configuration's own count:
FLOPs a sample requires (``<ops_count>.flops_per_sample``, from the shapes:
no layer's work depends on its input) x samples/s of this run, over the
peak.  (``hybrid.mfu_pct``'s reader wants a count that names a state-space
scan, ``torso.mfu_pct``'s one that counts routed pairs.)"""
import importlib


def read(r):
    rate = r.end_to_end.get("learn_samples_per_s")
    if rate is None:
        return None
    ops = importlib.import_module(r.config["ops_count"])
    chips = int(r.config.get("data_parallel", 1))
    return ops.flops_per_sample(r.config) * rate / (chips * r.peaks["flops_per_s_bf16"]) * 100.0
