"""The latent layer's kernels' share of their roofline
(``<ops_count>.attention_floor_s``: FLOPs of the two score products and the
values over the pairs in the causal mask over the peak, or the reads of both
query parts, the keys, the one shared key and the values and the write of the
output) over the device time on ``torso:attn_latent`` (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.roofline(r, "attn_latent", "attention_floor_s", "latent")
