"""The delta-rule scans' share of their roofline: the least time a step's scans
can take (``<ops_count>.delta_floor_s``: the chunked form's products over the
in-chunk pairs over the peak, or the reads of q, k, v, g and beta and the
write of o over the bandwidth) over the device time on instructions scoped
``torso:delta_scan``."""
import parts_times


def read(r):
    return parts_times.roofline(r, "delta_scan", "delta_floor_s")
