"""The delta-rule scans' share of their roofline: the least time a step's scans
can take (``<ops_count>.delta_floor_s``) over the device time on instructions
scoped ``torso:delta_scan``."""
import parts_times


def read(r):
    return parts_times.roofline(r, "delta_scan", "delta_floor_s")
