"""The fused scan's share of its roofline: the least time one step can take
on one chip (operations over peak FLOP/s or bytes over peak bytes/s,
whichever is larger, ops_count.step_floor_s) over its device time."""


def read(r):
    seconds, runs = r.trace_reduce.module_seconds(
        r.trace, r.fused_program, *r.trace_reduce.span_window(r.trace))
    if not runs:
        return None
    floor_s, _bound = r.ops_count.step_floor_s(r.config, r.peaks)
    return floor_s / (seconds / (runs * r.config["steps_per_call"])) * 100.0
