"""Device microseconds per learner step on instructions scoped
``torso:ssm_scan``: the state-space layers' chunked scans (the chunking, running
sums and decays, the products inside and across chunks), forward,
recomputation and backward (``hybrid_times.py``)."""
import hybrid_times


def read(r):
    return hybrid_times.read(r, "ssm_scan")
