"""Device microseconds per learner step on ops of stage ``forward`` outside
``pass:bootstrap``: the differentiated forward on ``obs``, the loss and the
casts (``pass_times.py``)."""
import pass_times


def read(r):
    return pass_times.read(r, "forward")
