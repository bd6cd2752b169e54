"""The chunked scans' share of their roofline: the least time a step's scans can
take (``<ops_count>.scan_floor_s``: the chunked form's products over the
peak, or the reads of x, B, C and dt and the write of y over the bandwidth)
over the device time on instructions scoped ``torso:ssm_scan``."""
import hybrid_times


def read(r):
    return hybrid_times.roofline(r, "ssm_scan", "scan_floor_s")
