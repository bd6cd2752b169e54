"""Tracing/profiling subsystem (SURVEY §5: absent in the reference).

One vocabulary, three uses:

  * ``STAGES`` / ``stage(name)`` — the names of the fused learner's stages
    and the ``jax.named_scope("stage:<name>")`` the replay and learner code
    wraps each in.  Scopes are metadata on the compiled program's
    instructions: always on, no operation added.  AD turns the one scope
    around the loss into ``jvp(stage:forward)`` (forward) and
    ``transpose(jvp(stage:forward))`` (backward).
  * ``jit_fused`` / ``fused_hlo_text(name)`` — the fused builders jit through
    ``jit_fused``, which remembers the abstract signature each program was
    last traced with (in the traced body: once per compile, nothing per
    call), so the optimized HLO text — the only place that maps a device
    trace's instruction names to their scopes — can be had afterwards:
    lowered again from that signature, jit answers with the executable
    that ran.
  * ``StageTimer`` — per-component wall-clock accumulators for the host-side
    pipeline stages (``stage_us`` in the runtime's JSONL metrics).  Each
    ``stage(name)`` is also a ``jax.profiler.TraceAnnotation("apex:<name>")``,
    so a profiler trace of a live trainer shows the host stages beside the
    device ops, on one clock.

``trace(logdir)`` wraps ``jax.profiler`` device tracing (a profiler that
cannot start raises: a run asked to trace either produces a trace or
fails); ``summarize_trace(logdir)`` reduces such a trace with
``jax.profiler.ProfileData``: device busy share, seconds per stage, the
longest device gaps named by the ``apex:*`` span beside each.

The reference has no profiling at all (``time`` is imported in its
learner.py:3 solely for ``sleep`` — reference SURVEY §5).
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import functools
import glob
import os
import re
import threading
import time
import types
from collections import defaultdict
from typing import Dict, Iterator, Optional

STAGES = ("ingest", "sample", "gather", "forward", "backward", "optimizer",
          "restamp", "target_sync")
SCOPE_PREFIX = "stage:"   # device side: jax.named_scope("stage:<name>")
# The parts of a network, under a prefix of their own: the readers of a
# stage take the innermost ``stage:`` of an op, and a part nested under that
# prefix would take the network's time out of ``forward`` and lose its
# backward pass.  A part is read beside its stage, not in its place.
PARTS = ("stem", "mixer", "router", "experts", "dense_ffn", "head",
         "attn_window", "attn_full", "shared_expert", "ssm_scan")
PART_PREFIX = "torso:"    # device side: jax.named_scope("torso:<name>")
SPAN_PREFIX = "apex:"     # host side: TraceAnnotation("apex:<name>")


def stage(name: str):
    """``jax.named_scope("stage:<name>")`` for a name in ``STAGES``."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; STAGES = {STAGES}")
    import jax

    return jax.named_scope(SCOPE_PREFIX + name)


def part(name: str):
    """``jax.named_scope("torso:<name>")`` for a name in ``PARTS``."""
    if name not in PARTS:
        raise ValueError(f"unknown part {name!r}; PARTS = {PARTS}")
    import jax

    return jax.named_scope(PART_PREFIX + name)


class StageTimer:
    """Named wall-clock accumulators: ``with timer.stage("sample"): ...``.

    Cheap enough for hot loops (one ``perf_counter`` pair per section plus
    one uncontended lock acquire — the ``+=`` on a dict item is a
    read-modify-write, NOT atomic under CPython, so cross-thread updates
    need the lock to not lose counts).
    """

    def __init__(self):
        self._total_s: Dict[str, float] = defaultdict(float)
        self._count: Dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        """Time the section and put the ``apex:<name>`` span on the
        profiler's clock; with no profiler session a TraceAnnotation is a
        flag test."""
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self._total_s[name] += dt
                self._count[name] += 1

    def us_per_call(self) -> Dict[str, float]:
        with self._lock:  # readers too: a concurrent first-use of a stage
            # name inserts into the defaultdict mid-iteration otherwise
            totals, counts = dict(self._total_s), dict(self._count)
        return {
            name: round(totals[name] / max(1, counts[name]) * 1e6, 1)
            for name in totals
        }

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            totals, counts = dict(self._total_s), dict(self._count)
        return {
            name: {
                "total_s": round(totals[name], 4),
                "calls": counts[name],
                "us_per_call": round(
                    totals[name] / max(1, counts[name]) * 1e6, 1
                ),
            }
            for name in totals
        }

    def reset(self) -> None:
        with self._lock:
            self._total_s.clear()
            self._count.clear()


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """``jax.profiler`` device trace into ``logdir`` (TensorBoard format):
    device ops, host threads and ``TraceAnnotation``s (``apex:<stage>``)
    on one clock.  The Python tracer is off, as in ``benchmark/run.py``:
    with it a trace of a trainer and its actor threads is mostly Python
    frames (187 MB of xplane against 82 MB for chip_smoke's thread leg).

    Raises whatever ``start_trace`` / ``stop_trace`` raise: a caller that
    asked for a trace must not get a green run and no trace."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ------------------------------------------------- the fused programs' text

class _FusedProgram:
    """One jitted fused entry point and the abstract signature it was last
    traced with."""

    def __init__(self, traced, jit_kwargs):
        import jax

        self.traced, self.jit_kwargs = traced, jit_kwargs
        self.jitted = jax.jit(traced, **jit_kwargs)
        self.signature = None  # args as ShapeDtypeStructs, shardings included
        self.text: Optional[str] = None

    def hlo_text(self) -> str:
        """The optimized HLO text of the program, lowered from the recorded
        signature: jit answers with the executable that ran.  The persistent
        compile cache's key leaves metadata out, so an executable loaded
        from an entry that a build without the scopes wrote runs the same
        instructions and names no stage; then the same function is jitted
        and compiled once more under a key that holds the metadata (a real
        compile the first time, a cache load after).  The flag is set for
        this thread alone (jax's config states are thread-local inside
        their context managers): a compile another thread starts meanwhile
        keeps its key.  Under ``shard_map`` the compiler derives some
        instruction names from ``op_name``, so the second text may miss a
        few of the ops that ran: the readers report the share it names."""
        import jax
        from jax._src.config import compilation_cache_include_metadata_in_key

        if self.text is None:
            text = self.jitted.lower(*self.signature).compile().as_text()
            if SCOPE_PREFIX not in text:
                again = jax.jit(  # a new function: nothing in memory answers
                    functools.wraps(self.traced)(lambda *a: self.traced(*a)),
                    **self.jit_kwargs)
                with compilation_cache_include_metadata_in_key(True):
                    text = again.lower(*self.signature).compile().as_text()
            self.text = text
        return self.text


_KEEP = 4  # programs remembered per name: a trainer builds one, a
#            benchmark run two (the window's and the comparison's)
_fused_programs: Dict[str, collections.deque] = {}


def jit_fused(fn, mesh=None, arg_specs=None, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` for a fused learner entry point, kept
    under the name its runs carry in a device trace (``jit_<fn.__name__>``)
    with the abstract signature of its latest trace, for ``fused_hlo_text``.

    The signature is recorded in the traced Python body: once per compile,
    never per call.  A sharded builder adds what a tracer does not know:
    ``arg_specs`` holds, per positional argument, the ``PartitionSpec`` (or
    a pytree of them matching the argument) its callers commit it to on
    ``mesh``, or None for one that arrives uncommitted.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def abstract(x, spec=None):
        aval = jax.typeof(x)
        return jax.ShapeDtypeStruct(
            aval.shape, aval.dtype,
            sharding=None if spec is None else NamedSharding(mesh, spec),
            weak_type=getattr(aval, "weak_type", False),
        )

    def abstract_arg(arg, spec):
        if spec is None or isinstance(spec, PartitionSpec):
            return jax.tree_util.tree_map(lambda x: abstract(x, spec), arg)
        return jax.tree_util.tree_map(abstract, arg, spec)

    @functools.wraps(fn)
    def traced(*args):
        prog.signature = tuple(map(
            abstract_arg, args, arg_specs or (None,) * len(args)))
        prog.text = None
        return fn(*args)

    prog = _FusedProgram(traced, jit_kwargs)
    name = "jit_" + getattr(fn, "__name__", "fused")
    _fused_programs.setdefault(
        name, collections.deque(maxlen=_KEEP)).append(prog)
    return prog.jitted


def fused_hlo_texts(name: str) -> Iterator[str]:
    """The optimized HLO text (``_FusedProgram.hlo_text``) of every
    remembered program called ``name`` that has been traced, oldest first,
    each made when the iterator reaches it."""
    for prog in list(_fused_programs.get(name, ())):
        if prog.signature is not None:
            yield prog.hlo_text()


def fused_hlo_text(name: str) -> str:
    """The text of the newest traced program called ``name`` (the name its
    runs carry in a device trace, ``jit_<function>``)."""
    traced = [p for p in _fused_programs.get(name, ()) if p.signature is not None]
    if not traced:
        raise KeyError(f"no fused program named {name!r} has been traced; "
                       f"known: {sorted(_fused_programs)}")
    return traced[-1].hlo_text()


# ------------------------------------------------------ reducing a trace

OTHER = "other"
_NO_EVENTS = types.SimpleNamespace(events=())
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(re.escape(SCOPE_PREFIX) + r"(\w+)")
_BACKWARD = re.compile(r"transpose\([^/]*" + re.escape(SCOPE_PREFIX) + "forward")


def hlo_stages(hlo_text: str) -> Dict[str, str]:
    """{instruction: stage} for every instruction of an executable's HLO
    text: the innermost ``stage:<name>`` of its own ``op_name`` (``forward``
    under ``transpose(`` is ``backward``), else ``other``.  The operator's
    summary stops there: the compiler's unscoped layout copies are
    ``other`` here, and the one rule that hands each to the stage that
    consumes it is the benchmark's (``benchmark/stage_times.py``), whose
    test holds the two to the same answer on every scoped instruction."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if not m or m.group(1) in out:
            continue
        op = _HLO_OP_NAME.search(m.group(2))
        found = _SCOPE.findall(op.group(1)) if op else []
        out[m.group(1)] = (
            OTHER if not found else
            "backward" if found[-1] == "forward" and _BACKWARD.search(op.group(1))
            else found[-1])
    return out


_PART = re.compile(re.escape(PART_PREFIX) + r"(\w+)")


def hlo_parts(hlo_text: str) -> Dict[str, str]:
    """{instruction: part} for the instructions whose own ``op_name`` holds a
    ``torso:<name>`` (the innermost), forward, recomputation and backward
    alike."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if not m or m.group(1) in out:
            continue
        op = _HLO_OP_NAME.search(m.group(2))
        found = _PART.findall(op.group(1)) if op else []
        if found:
            out[m.group(1)] = found[-1]
    return out


def _merged(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _own_seconds(events) -> list:
    """(name, start, own seconds) per event: its length less the events
    nested in it (a ``while`` covers its body's ops).  ``events``: (name,
    start, end)."""
    out, stack = [], []  # stack: [name, start, end, seconds under children]

    def close(item):
        name, start, end, covered = item
        out.append((name, start, max(0.0, end - start - covered)))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], ev[1] - ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    for item in stack:
        close(item)
    return out


def summarize_trace(logdir: str) -> dict:
    """Reduce the newest ``*.xplane.pb`` under ``logdir`` with
    ``jax.profiler.ProfileData``: the share of the traced span with an op
    running on the device; seconds per stage (and per part of the network,
    ``part_s``, where the program names parts), from the ops inside the runs
    of every fused program whose text is known (``fused_hlo_texts``; ops of
    any other program, the actors' action selection say, are
    ``other_programs``), with the share of that time on instructions the
    text holds (``stage_named_share``: below 1 the text is not quite the
    executable's that ran); and the five longest device gaps, each named by
    the ``apex:<stage>`` host span that overlaps it most.  Averaged over
    chips.  The first summary after a stale compile-cache load pays one
    compile of the fused program on the calling thread
    (``_FusedProgram.hlo_text``)."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    stages: Dict[str, Dict[str, str]] = {}  # program -> instruction -> stage
    parts: Dict[str, Dict[str, str]] = {}   # program -> instruction -> part
    for program in list(_fused_programs):
        for text in fused_hlo_texts(program):
            stages.setdefault(program, {}).update(hlo_stages(text))
            parts.setdefault(program, {}).update(hlo_parts(text))

    def events(line) -> list:
        return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events]

    devices, spans = [], []
    for plane in ProfileData.from_file(found[-1]).planes:
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" in lines:
            devices.append((events(lines["XLA Ops"]),
                            events(lines.get("XLA Modules", _NO_EVENTS))))
        elif plane.name == "/host:CPU":
            spans += [ev for line in plane.lines for ev in events(line)
                      if ev[0].startswith(SPAN_PREFIX)]
    if not devices:
        raise ValueError("the trace holds no device plane with an XLA Ops line")
    busy = span = fused_s = named_s = 0.0
    stage_s: Dict[str, float] = defaultdict(float)
    part_s: Dict[str, float] = defaultdict(float)
    idle: list = []
    for ops, modules in devices:
        if not ops:
            continue
        merged = _merged((s, e) for _n, s, e in ops)
        busy += sum(e - s for s, e in merged)
        span += merged[-1][1] - merged[0][0]
        idle += [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
        runs = sorted((s, e, name.split("(", 1)[0]) for name, s, e in modules
                      if name.split("(", 1)[0] in stages)
        starts = [r[0] for r in runs]
        for hlo, start, own in _own_seconds(ops):
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < runs[i][1]:
                name = hlo.split(" = ", 1)[0].lstrip("%").strip()
                stage = stages[runs[i][2]].get(name, OTHER)
                if name in parts[runs[i][2]]:
                    part_s[parts[runs[i][2]][name]] += own / len(devices)
                fused_s += own
                named_s += own if name in stages[runs[i][2]] else 0.0
            else:
                stage = "other_programs"
            stage_s[stage] += own / len(devices)

    def beside(t0: float, t1: float) -> str:
        best, best_s = "host:unattributed", 0.0
        for name, s, e in spans:
            overlap = min(t1, e) - max(t0, s)
            if overlap > best_s:
                best, best_s = name, overlap
        return best

    return {
        "xplane": found[-1],
        "devices": len(devices),
        "device_busy_share": busy / span if span else 0.0,
        "stage_s": {k: round(v, 6) for k, v in sorted(stage_s.items())},
        # seconds on instructions a part of the network names (PARTS),
        # forward and backward together; a part is read beside its stage
        "part_s": {k: round(v, 6) for k, v in sorted(part_s.items())},
        "stage_named_share": named_s / fused_s if fused_s else None,
        "host_spans": len(spans),
        "longest_gaps": [[beside(t0, t1), round(length, 6)]
                         for length, t0, t1 in sorted(idle, reverse=True)[:5]],
    }
