"""Model FLOP/s utilisation with the configuration's own count: FLOPs a
sample requires (``<ops_count>.flops_per_sample``, the experts from the
pairs the window's calls routed to held experts) x samples/s of this run,
over the peak."""
import importlib


def read(r):
    rate = r.end_to_end.get("learn_samples_per_s")
    pairs = r.counters.get("held_pairs_per_step")
    if rate is None or pairs is None or "ops_count" not in r.config:
        return None
    ops = importlib.import_module(r.config["ops_count"])
    chips = int(r.config.get("data_parallel", 1))
    return (ops.flops_per_sample(r.config, pairs) * rate
            / (chips * r.peaks["flops_per_s_bf16"]) * 100.0)
