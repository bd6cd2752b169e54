"""Device microseconds per learner step spent ingesting: the fused program's ops
scoped ``stage:ingest`` plus the whole runs, in the traced window, of every
other program (the dedup layouts' two ingest programs; the per-call key split
lands here too)."""
import stage_times


def read(r):
    return stage_times.read(r, "ingest")
