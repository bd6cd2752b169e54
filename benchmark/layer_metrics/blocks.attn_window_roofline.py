"""The window layers' attention kernels' share of their roofline: the least time
a step's masked products can take (``<ops_count>.attention_floor_s``: FLOPs of
the pairs in the mask over the peak, or the reads and writes of q, k, v and
the output) over the device time on instructions scoped ``torso:attn_window``."""
import blocks_times


def read(r):
    return blocks_times.attention_roofline(r, "window")
