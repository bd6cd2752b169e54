"""The attention layer's kernels' share of their roofline
(``<ops_count>.attention_floor_s`` at 8 query heads on one key-value head:
FLOPs of the pairs in the causal mask over the peak, or the reads and writes
of q, k, v and the output) over the device time on instructions scoped
``torso:attn_full`` (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.roofline(r, "attn_full", "attention_floor_s", "full")
