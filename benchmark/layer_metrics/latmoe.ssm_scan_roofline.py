"""The grouped scans' share of their roofline: the least time a step's scans
can take (``<ops_count>.scan_floor_s``: the chunked form's products at chunk
128 with the scores once a group held, over the peak, or the reads of x, B, C
and dt and the write of y over the bandwidth) over the device time on
instructions scoped ``torso:ssm_scan`` (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.roofline(r, "ssm_scan", "scan_floor_s")
