"""From a profiler trace (``*.xplane.pb``) to numbers, read with
``jax.profiler.ProfileData`` alone.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per program
run, named ``jit_<function>(<fingerprint>)``) and ``XLA Ops`` (one event per
HLO op, named by its HLO text ``%fusion.3 = ...``; a ``while`` covers the ops
of its body) and ``Async XLA Ops`` (copies and collectives in flight); the
host's threads are lines of ``/host:CPU``, and a ``TraceAnnotation`` is an
event there under its own name, on the same clock.

Busy time is the union of the ``XLA Ops`` intervals; idle is the window less
that.  An op's time in the table of top ops is its own: what its interval
does not share with ops nested inside it.  A gap is named after the host
annotation (``bench:...``) that overlaps it most.
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, NamedTuple

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


class Event(NamedTuple):
    name: str
    start: float  # seconds on the trace's clock
    end: float


class DeviceTrace(NamedTuple):
    ops: list      # Events of the XLA Ops line
    async_ops: list
    modules: list


class Trace(NamedTuple):
    devices: dict  # plane name -> DeviceTrace
    spans: list    # host annotations named bench:*


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    return found[-1]


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def _events(line) -> list:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = DeviceTrace(
                ops=_events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                async_ops=_events(lines[ASYNC_LINE]) if ASYNC_LINE in lines else [],
                modules=_events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
            )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(e for e in _events(line) if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda e: e.start)
    return Trace(devices, spans)


def clip(events: Iterable[Event], t0: float, t1: float) -> list:
    return [Event(e.name, max(e.start, t0), min(e.end, t1))
            for e in events if e.end > t0 and e.start < t1]


def union(events: Iterable[Event]) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted((ev.start, ev.end) for ev in events):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(events: Iterable[Event]) -> float:
    return sum(e - s for s, e in union(events))


def self_times(events: Iterable[Event]) -> dict:
    """Seconds per op name, each event less the events nested inside it."""
    totals: dict = {}
    stack: list = []  # [event, seconds covered by children]

    def close(item):
        ev, covered = item
        name = op_name(ev.name)
        totals[name] = totals.get(name, 0.0) + max(0.0, (ev.end - ev.start) - covered)

    for ev in sorted(events, key=lambda e: (e.start, -(e.end - e.start))):
        while stack and stack[-1][0].end <= ev.start:
            close(stack.pop())
        if stack:
            stack[-1][1] += min(ev.end, stack[-1][0].end) - ev.start
        stack.append([ev, 0.0])
    while stack:
        close(stack.pop())
    return totals


def gaps(events: Iterable[Event], t0: float, t1: float) -> list:
    """Idle (start, end) intervals of one device inside [t0, t1]."""
    out, at = [], t0
    for s, e in union(clip(events, t0, t1)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return out


def name_gap(gap: tuple, spans: Iterable[Event]) -> str:
    best, best_overlap = "host:unattributed", 0.0
    for sp in spans:
        overlap = min(gap[1], sp.end) - max(gap[0], sp.start)
        if overlap > best_overlap:
            best, best_overlap = sp.name, overlap
    return best


def span_window(trace: Trace, edge: str = SPAN_PREFIX + "force") -> tuple:
    """The steady part of the traced window: from the end of the first
    ``bench:force`` span (the first traced call has completed, the next is
    running) to the end of the last one."""
    ends = sorted(sp.end for sp in trace.spans if sp.name == edge)
    if len(ends) < 2:
        raise ValueError(f"the trace holds {len(ends)} {edge} spans; two bound the window")
    return ends[0], ends[-1]


def module_seconds(trace: Trace, module: str, t0: float, t1: float) -> tuple:
    """(device seconds, runs) of programs named ``<module>(...)``: the runs
    that lie whole inside the window, summed, and averaged over chips."""
    secs, runs = [], []
    for dev in trace.devices.values():
        evs = [e for e in dev.modules
               if e.start >= t0 and e.end <= t1 and e.name.split("(", 1)[0] == module]
        secs.append(sum(e.end - e.start for e in evs))
        runs.append(len(evs))
    if not secs:
        return 0.0, 0
    return sum(secs) / len(secs), max(runs)


def summarize(trace: Trace, top: int = 10) -> dict:
    """Busy and window seconds averaged over the chips, the share of the
    window with a collective running, the top ops by own time and the
    longest idle gaps named by host span."""
    if not trace.devices:
        raise ValueError("the trace holds no /device:TPU plane")
    t0, t1 = span_window(trace)
    window = t1 - t0
    busy, coll, ops_total, gap_list = [], [], {}, []
    for dev in trace.devices.values():
        ops = clip(dev.ops, t0, t1)
        busy.append(busy_seconds(ops))
        both = ops + clip(dev.async_ops, t0, t1)
        coll.append(busy_seconds(
            e for e in both if op_name(e.name).startswith(COLLECTIVES)
        ))
        for name, s in self_times(ops).items():
            ops_total[name] = ops_total.get(name, 0.0) + s / len(trace.devices)
        gap_list.extend(gaps(dev.ops, t0, t1))
    gap_list.sort(key=lambda g: g[0] - g[1])
    n = len(busy)
    return {
        "window_s": window,
        "busy_s": sum(busy) / n,
        "collective_s": sum(coll) / n,
        "device_ops": [[k, v] for k, v in
                       sorted(ops_total.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name_gap(g, trace.spans), g[1] - g[0]] for g in gap_list[:top]],
    }
