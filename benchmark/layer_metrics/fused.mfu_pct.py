"""Model FLOP/s utilisation: FLOPs a sample requires x samples/s of this
run, over chips x peak."""


def read(r):
    rate = r.end_to_end.get("learn_samples_per_s")
    if rate is None:
        return None
    chips = int(r.config.get("data_parallel", 1))
    return (r.ops_count.flops_per_sample(r.config) * rate
            / (chips * r.peaks["flops_per_s_bf16"]) * 100.0)
