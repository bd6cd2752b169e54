"""Device microseconds per learner step on instructions scoped
``torso:ssm_scan``: the Mamba-2 layers' chunked scans over the held groups (the running
sums and decays, the products inside and across chunks), forward,
recomputation and backward (``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "ssm_scan")
