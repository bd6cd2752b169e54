"""Share of the traced window with a collective running, per chip."""


def read(r):
    if int(r.config.get("data_parallel", 1)) < 2:
        return None
    s = r.trace_summary
    return s["collective_s"] / s["window_s"] * 100.0
