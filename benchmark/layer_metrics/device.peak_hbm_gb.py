"""Peak bytes in use on the fullest chip, in GB."""


def read(r):
    return r.device["memory_peak_bytes"] / 1e9
