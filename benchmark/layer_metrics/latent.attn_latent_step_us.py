"""Device microseconds per learner step on the latent layer's attention
kernels (``torso:attn_latent``): forward, dq and dk/dv with the shared key
operand, and the sum of the shared key's gradient over the heads
(``parts_times.py``)."""
import parts_times


def read(r):
    return parts_times.read(r, "attn_latent")
