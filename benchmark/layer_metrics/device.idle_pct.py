"""Share of the traced window with no operation on the device."""


def read(r):
    s = r.trace_summary
    return (1.0 - s["busy_s"] / s["window_s"]) * 100.0
