"""The whole step's share of the peak with the configuration's own count:
FLOPs a sample requires (``<ops_count>.flops_per_sample``, from the shapes:
no layer's work depends on its input) x samples/s of this run, over the
peak (``hybrid_times.mfu``)."""
import hybrid_times


def read(r):
    return hybrid_times.mfu(r)
