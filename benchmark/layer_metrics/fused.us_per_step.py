"""Device microseconds per learner step: the device time of the fused
program's runs that lie whole inside the traced window, over runs x K."""


def read(r):
    seconds, runs = r.trace_reduce.module_seconds(
        r.trace, r.fused_program, *r.trace_reduce.span_window(r.trace))
    if not runs:
        return None
    return seconds / (runs * r.config["steps_per_call"]) * 1e6
