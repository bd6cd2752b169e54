"""Device microseconds per learner step on ops of stage ``forward`` under
``pass:bootstrap``: the two forwards on ``next_obs``, the online and the
target network's, which no gradient reaches (``pass_times.py``)."""
import pass_times


def read(r):
    return pass_times.read(r, "bootstrap")
