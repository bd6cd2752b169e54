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

  * ``launch`` — the process's launch log (``LaunchLog``): host spans from
    the package's import to the first learner step whose result the host
    waited for.  The imports (a finder at the front of ``sys.meta_path``),
    the chip's start-up, the builders, every compile by program and phase
    (listeners on ``jax.monitoring``, registered by
    ``enable_compile_cache``) and the loop's own ``apex:<stage>`` spans, on
    ``time.perf_counter``.  ``launch.summary()`` partitions the launch
    thread's time into nine parts that add up; a compile after
    ``launch.done(step)`` is a recompile.  Nothing here runs per call.

``trace(logdir)`` wraps ``jax.profiler`` device tracing (a profiler that
cannot start raises: a run asked to trace either produces a trace or
fails); ``summarize_trace(logdir)`` reduces such a trace with
``jax.profiler.ProfileData``: device busy share, seconds per stage, the
longest device gaps named by the ``apex:*`` span beside each.

The reference has no profiling at all (``time`` is imported in its
learner.py:3 solely for ``sleep`` — reference SURVEY §5).
"""

from __future__ import annotations

import atexit
import bisect
import collections
import contextlib
import functools
import glob
import json
import os
import re
import sys
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
         "attn_window", "attn_full", "shared_expert", "ssm_scan", "delta_scan", "attn_latent",
         "latent_proj")
PART_PREFIX = "torso:"    # device side: jax.named_scope("torso:<name>")
# The passes of a step, a third axis beside stages and parts and under a
# prefix of its own for the same reason: the stage readers take the innermost
# ``stage:``, the part readers the innermost ``torso:``, and a pass nested
# under either prefix would move them.  Two scopes are written by hand,
# ``pass:bootstrap`` (the forwards on ``next_obs``) and ``pass:again`` (a
# hand-written backward's forward, computed again); AD writes the rest:
# ``rematted_computation`` is jax's own segment for what a checkpoint computes
# again, ``transpose(`` its mark of a pull-back.
PASSES = ("bootstrap", "forward", "recompute", "backward")
PASS_SCOPES = ("bootstrap", "again")
PASS_PREFIX = "pass:"     # device side: jax.named_scope("pass:<name>")
REMAT_SEGMENT = "rematted_computation"  # jax's name-stack segment (ad_checkpoint.py)
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


def pass_(name: str):
    """``jax.named_scope("pass:<name>")`` for a name in ``PASS_SCOPES``."""
    if name not in PASS_SCOPES:
        raise ValueError(f"unknown pass scope {name!r}; PASS_SCOPES = {PASS_SCOPES}")
    import jax

    return jax.named_scope(PASS_PREFIX + name)


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

    def stage(self, name: str):
        """Time the section and put the ``apex:<name>`` span on the
        profiler's clock; with no profiler session a TraceAnnotation is a
        flag test.  Until the launch is done the span is in the launch log
        too (``LaunchLog._span``, the one implementation)."""
        return launch._span(SPAN_PREFIX + name, SPAN_PREFIX + name, None,
                            functools.partial(self._add, name))

    def _add(self, name: str, dt: float) -> None:
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
        from an entry that a build with other scopes wrote runs the same
        instructions under that build's names: none from a build before the
        stages, ``stage:`` and no ``pass:`` from one before the passes (a
        parent commit measured before the change on one machine and one
        cache: its entry answers the change's compile).  A fused program
        holds a train step, whose loss emits both prefixes, so a text that
        lacks either is such a load; then the same function is jitted and
        compiled once more under a key that holds the metadata (a real
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
            if SCOPE_PREFIX not in text or PASS_PREFIX not in text:
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

    name = "jit_" + getattr(fn, "__name__", "fused")
    with launch.span("fused_program", program=name):
        prog = _FusedProgram(traced, jit_kwargs)
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


def _own_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction: its own ``op_name``, "" without one} for every
    instruction of an executable's HLO text."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m and m.group(1) not in out:
            op = _HLO_OP_NAME.search(m.group(2))
            out[m.group(1)] = op.group(1) if op else ""
    return out


def _op_stage(op_name: str) -> str:
    found = _SCOPE.findall(op_name)
    if not found:
        return OTHER
    if found[-1] == "forward" and _BACKWARD.search(op_name):
        return "backward"
    return found[-1]


def hlo_stages(hlo_text: str) -> Dict[str, str]:
    """{instruction: stage} for every instruction of an executable's HLO
    text: the innermost ``stage:<name>`` of its own ``op_name`` (``forward``
    under ``transpose(`` is ``backward``), else ``other``.  The operator's
    summary stops there: the compiler's unscoped layout copies are
    ``other`` here, and the one rule that hands each to the stage that
    consumes it is the benchmark's (``benchmark/stage_times.py``), whose
    test holds the two to the same answer on every scoped instruction."""
    return {name: _op_stage(op) for name, op in _own_op_names(hlo_text).items()}


_PART = re.compile(re.escape(PART_PREFIX) + r"(\w+)")


def hlo_parts(hlo_text: str) -> Dict[str, str]:
    """{instruction: part} for the instructions whose own ``op_name`` holds a
    ``torso:<name>`` (the innermost), forward, recomputation and backward
    alike."""
    found = ((name, _PART.findall(op)) for name, op in _own_op_names(hlo_text).items())
    return {name: parts[-1] for name, parts in found if parts}


# ``pass:again`` as a segment of its own: the pull-back of what ran under it
# reads ``transpose(pass:again)``, and is the backward pass proper.
_AGAIN = re.compile(r"(?:^|/)(?:" + re.escape(REMAT_SEGMENT) + "|"
                    + re.escape(PASS_PREFIX) + r"again)(?:/|$)")


def op_pass(op_name: str) -> Optional[str]:
    """The pass of ``PASSES`` that an ``op_name`` names, or None where its
    stage is neither ``forward`` nor ``backward``: ``bootstrap`` under
    ``pass:bootstrap``; under the pull-back ``recompute`` where jax's
    ``rematted_computation`` or ``pass:again`` is a segment (nested
    recomputation is ``recompute`` once), else ``backward``; else
    ``forward``."""
    stage = _op_stage(op_name)
    if stage not in ("forward", "backward"):
        return None
    if PASS_PREFIX + "bootstrap" in op_name:
        return "bootstrap"
    if stage == "forward":
        return "forward"
    return "recompute" if _AGAIN.search(op_name) else "backward"


def hlo_passes(hlo_text: str) -> Dict[str, str]:
    """{instruction: pass} for the instructions whose own ``op_name`` puts
    them in ``forward`` or ``backward`` (``hlo_stages``' rule), by
    ``op_pass``.  As ``hlo_stages``, the operator's summary stops at an
    instruction's own metadata; the benchmark's ``pass_times.py`` hands the
    unscoped ones on, and its test holds the two to one answer here."""
    found = ((name, op_pass(op)) for name, op in _own_op_names(hlo_text).items())
    return {name: p for name, p in found if p}


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_RESULT = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_HLO_ARRAY = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_ASYNC_FUSION_DONE = "async-collective-done"  # the TPU compiler's fused form


def _hlo_bytes(shape_text: str) -> int:
    """Bytes of every array in an instruction's result shape."""
    total = 0
    for dtype, dims in _HLO_ARRAY.findall(shape_text):
        bits = 8 if dtype == "pred" else int(re.sub(r"\D", "", dtype))
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * bits // 8
    return total


def hlo_collectives(hlo_text: str) -> Dict[str, Dict[str, Dict[str, int]]]:
    """The collectives of an executable's HLO text by kind and by how they
    run: ``{kind: {"sync": {"count", "bytes"}, "async": {"count", "bytes"}}}``
    for the kinds in ``COLLECTIVES`` the text holds.  Synchronous: an
    instruction with the kind as its opcode, outside any fusion's computation
    (nothing else runs while it does).  Asynchronous: a ``<kind>-start`` /
    ``<kind>-done`` pair, or the TPU compiler's fused form of one, fusions
    named ``async-collective-start`` / ``async-collective-done`` whose
    computations hold the kind's steps; a pair is counted at its ``-done``,
    whose result is the collective's.  Instructions, not executions: one in a
    loop's body counts once.  Bytes are the result's, per device.  Computed
    from the text when asked (``chip_smoke.py``'s ``dp4`` leg); no run calls
    it."""
    fused = set()   # computations a fusion calls
    inside = {}     # computation -> the collective kinds it holds
    rows, name = [], None
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            name = head.group(1)
            continue
        m = _HLO_RESULT.match(line)
        if not m:
            continue
        instr, shape, op = m.groups()
        if op == "fusion":
            called = _HLO_CALLS.search(line)
            if called:
                fused.add(called.group(1))
        if op in COLLECTIVES:
            inside.setdefault(name, set()).add(op)
        rows.append((name, instr, shape, op, line))
    out: Dict[str, Dict[str, Dict[str, int]]] = {}

    def count(kind: str, how: str, shape: str) -> None:
        cell = out.setdefault(kind, {h: {"count": 0, "bytes": 0} for h in ("sync", "async")})
        cell[how]["count"] += 1
        cell[how]["bytes"] += _hlo_bytes(shape)

    for computation, instr, shape, op, line in rows:
        if computation in fused:
            continue
        if op in COLLECTIVES:
            count(op, "sync", shape)
        elif op.endswith("-done") and op[:-len("-done")] in COLLECTIVES:
            count(op[:-len("-done")], "async", shape)
        elif op == "fusion" and instr.startswith(_ASYNC_FUSION_DONE):
            called = _HLO_CALLS.search(line)
            for kind in sorted(inside.get(called.group(1) if called else None, ())):
                count(kind, "async", shape)
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
    start, end).  An event that outlasts the one it starts in ends with
    it, so the own seconds under one event add up to its length."""
    out, stack = [], []  # stack: [name, start, end, seconds under children]

    def close(item):
        name, start, end, covered = item
        out.append((name, start, max(0.0, end - start - covered)))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], ev[1] - ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            e = min(e, stack[-1][2])
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    for item in stack:
        close(item)
    return out


def summarize_trace(logdir: str) -> dict:
    """Reduce the newest ``*.xplane.pb`` under ``logdir`` with
    ``jax.profiler.ProfileData``: the share of the traced span with an op
    running on the device; seconds per stage, ``stage_s`` (and per part of
    the network, ``part_s``, where the program names parts; per pass of the
    step, ``pass_s``, the ``PASSES`` of ``forward`` and ``backward`` apart:
    how much of the step is work done a second time), from the ops inside the runs
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
    passes: Dict[str, Dict[str, str]] = {}  # program -> instruction -> pass
    for program in list(_fused_programs):
        for text in fused_hlo_texts(program):
            stages.setdefault(program, {}).update(hlo_stages(text))
            parts.setdefault(program, {}).update(hlo_parts(text))
            passes.setdefault(program, {}).update(hlo_passes(text))

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
    pass_s: Dict[str, float] = defaultdict(float)
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
                if name in passes[runs[i][2]]:
                    pass_s[passes[runs[i][2]][name]] += own / len(devices)
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
        # seconds of ``forward`` and ``backward`` by pass of the step (PASSES)
        "pass_s": {k: round(v, 6) for k, v in sorted(pass_s.items())},
        "stage_named_share": named_s / fused_s if fused_s else None,
        "host_spans": len(spans),
        "longest_gaps": [[beside(t0, t1), round(length, 6)]
                         for length, t0, t1 in sorted(idle, reverse=True)[:5]],
    }


# ------------------------------------------------------------ the launch log

LAUNCH_PREFIX = SPAN_PREFIX + "launch:"  # TraceAnnotation("apex:launch:<name>")
# What ``LaunchLog.summary`` divides the launch thread's time into; the nine
# add up to the interval.
LAUNCH_PARTS = ("import_s", "chip_start_s", "trace_s", "lower_s",
                "cache_load_s", "compile_s", "build_s", "device_wait_s",
                "unattributed_s")
# The loads timed as ``import:<name>`` spans: the libraries a launch pays
# seconds for, and the package's own modules that pull them in first (their
# own seconds are what is left under them).
TIMED_IMPORTS = frozenset((
    "numpy", "jax", "jaxlib", "jax.experimental.pallas", "flax", "optax",
    "orbax.checkpoint",
    "ape_x_dqn_tpu.runtime", "ape_x_dqn_tpu.actors", "ape_x_dqn_tpu.serving",
    "ape_x_dqn_tpu.parallel", "ape_x_dqn_tpu.learner.train_step",
    "ape_x_dqn_tpu.models.dueling", "ape_x_dqn_tpu.models.expert_torso",
    "ape_x_dqn_tpu.models.lfm2_moe", "ape_x_dqn_tpu.models.laguna_moe",
    "ape_x_dqn_tpu.models.granite_hybrid", "ape_x_dqn_tpu.models.solar_open2",
    "ape_x_dqn_tpu.models.ling_hybrid", "ape_x_dqn_tpu.models.olmo_hybrid",
    "ape_x_dqn_tpu.models.kanana_moe", "ape_x_dqn_tpu.models.nemotron_h",
    "ape_x_dqn_tpu.replay.device",
    "ape_x_dqn_tpu.replay.device_dedup", "ape_x_dqn_tpu.utils.checkpoint",
))
LAUNCH_LOG_ENV = "APEX_LAUNCH_LOG"  # where the log is written at exit, if set
_MAX_SPANS = 8192       # a launch records one to two thousand (a benchmark
#                         process, which never ends its launch, up to six);
#                         past this they are counted (``dropped``), not kept
_KEEP_RECOMPILES = 32
_FOLD_TRACE_S = 0.0005  # a trace span shorter than this is counted (``folded``)
#                         and not kept: a jitted primitive found again in jax's
#                         trace cache (`add` inside a network's init: 14 us each,
#                         15,000 of them in a large torso's launch); its seconds
#                         stay with the span around it
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile:trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile:lower",
    "/jax/core/compile/backend_compile_duration": "compile:backend",
}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_WRAPPED = re.compile(r"^\w+\((.*)\)$")  # jit(fused) -> fused, as it is traced
# where the trainer's loops wait for a result (fused; one step at a time)
_WAIT_SPANS = (SPAN_PREFIX + "force_oldest", SPAN_PREFIX + "priority_writeback")
_ROOT, _MARKS = -1, -2  # keys of the interval and of the span read from marks


def _annotation(name: str):
    """``TraceAnnotation(name)`` once jax's profiler is loaded; before that
    (an import span opens ahead of jax) there is no trace to write into."""
    cls = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                  "TraceAnnotation", None)
    return contextlib.nullcontext() if cls is None else cls(name)


def _program_row() -> dict:
    """A row of ``LaunchLog.summary``'s ``programs`` before anything is
    added to it."""
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "cache": set(),
            "retrieval_s": 0.0, "compiles": 0}


def _part(name: str, attrs: dict) -> str:
    """The part of ``LAUNCH_PARTS`` a span's own seconds count under."""
    if name.startswith("import:"):
        return "import_s"
    if name == "backend":
        return "chip_start_s"
    if name == "compile:trace":
        return "trace_s"
    if name == "compile:lower":
        return "lower_s"
    if name == "compile:backend":
        return "cache_load_s" if attrs.get("cache") == "hit" else "compile_s"
    if name in _WAIT_SPANS:
        return "device_wait_s"
    return "build_s"  # builders, ring set-up, the loop's stages before `done`


class LaunchLog:
    """Spans of one process's launch, in memory: rows ``[name, start, end,
    parent, thread, attrs]`` on ``time.perf_counter``, ``parent`` the row of
    the innermost span open on the same thread when the row was made (for a
    compile span, made when it closes, the builder's span that holds it).
    The process id names the launch.  ``created`` pairs the two clocks once,
    to place the launch in wall time.

    Recording stops at ``done(step)``; from then a compile is a recompile,
    kept apart.  ``begin()`` starts a later launch in the same process."""

    def __init__(self):
        self.pid = os.getpid()
        self.created = (time.perf_counter(), time.time())
        self.thread = threading.get_ident()  # the launch thread: the one
        #                                      whose time `summary` divides
        self.step = 0  # the learner's step, as its loop last set it
        self.dropped = 0
        self.folded = 0
        self.compiles_after_launch = 0
        self.recompiles: collections.deque = collections.deque(
            maxlen=_KEEP_RECOMPILES)
        self._rows: list = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self._t0 = self.created[0]
        self._done: Optional[tuple] = None  # (perf_counter, step)
        self._written: Optional[float] = None  # a log read from a file ends here

    # -- recording --
    def _stack(self) -> list:
        return self._local.__dict__.setdefault("stack", [])

    def _append(self, name, start, end, attrs) -> Optional[int]:
        stack = self._stack()
        with self._lock:
            if len(self._rows) >= _MAX_SPANS:
                self.dropped += 1
                return None
            self._rows.append([name, start, end, stack[-1] if stack else None,
                               threading.get_ident(), attrs or {}])
            return len(self._rows) - 1

    @contextlib.contextmanager
    def _span(self, name, annotation, attrs=None, closed=None):
        """Time it, annotate it: the one implementation under ``span`` and
        ``StageTimer.stage``.  ``closed(seconds)`` is called at the end."""
        row = None
        t0 = time.perf_counter()
        if self._done is None:
            row = self._append(name, t0, None, attrs)
            if row is not None:
                self._stack().append(row)
        try:
            with _annotation(annotation):
                yield
        finally:
            t1 = time.perf_counter()
            if row is not None:
                self._rows[row][2] = t1
                self._stack().remove(row)
            if closed is not None:
                closed(t1 - t0)

    def span(self, name: str, **attrs):
        """Record ``name`` over the block and enter
        ``TraceAnnotation("apex:launch:<name>")``.  Never waits for the
        device."""
        return self._span(name, LAUNCH_PREFIX + name, attrs)

    def mark(self, name: str, **attrs) -> None:
        """A moment: a span of no length."""
        if self._done is None:
            t = time.perf_counter()
            self._append(name, t, t, attrs)

    def attrs_of(self, name: str) -> list:
        """The attributes of every span or mark called ``name``, in order."""
        with self._lock:
            return [dict(r[5]) for r in self._rows if r[0] == name]

    def _compile_span(self, name, wall_start, wall_end, fun_name) -> None:
        """One of jax's compile spans, just closed on this thread, moved
        from ``time.time`` to ``perf_counter`` by the clocks' distance now."""
        if name == "compile:trace" and wall_end - wall_start < _FOLD_TRACE_S:
            with self._lock:
                self.folded += 1
            return
        program = _WRAPPED.sub(r"\1", str(fun_name))
        off = time.perf_counter() - time.time()
        start, end = wall_start + off, wall_end + off
        attrs = {"program": program}
        if name == "compile:backend":
            note = self._local.__dict__.pop("cache", {})
            attrs["cache"] = note.get("cache", "off")
            if "retrieval_s" in note:
                attrs["retrieval_s"] = note["retrieval_s"]
        if self._done is None:
            self._append(name, start, end, attrs)
            return
        # After the launch: what this compile made its caller wait, phase
        # by phase, until the backend's span closes it.
        waited = self._local.__dict__.setdefault("waited", {})
        waited[program] = waited.get(program, 0.0) + end - start
        if name == "compile:backend":
            with self._lock:
                self.compiles_after_launch += 1
                self.recompiles.append({
                    "program": program, "step": self.step,
                    "seconds": round(waited[program], 6),
                    "cache": attrs["cache"],
                    "thread": threading.current_thread().name})
            waited.clear()

    def _cache_note(self, **note) -> None:
        """What the persistent cache said inside the backend span that is
        open on this thread; the span takes it when it closes."""
        self._local.__dict__.setdefault("cache", {}).update(note)

    # -- the launch's ends --
    def begin(self) -> None:
        """An entry point starts a launch.  The first of a process dates
        from the log's making, its imports included; after a ``done`` a new
        one starts here, with no compile after it yet."""
        with self._lock:
            if self._done is not None:
                self._t0, self._done = time.perf_counter(), None
                self.compiles_after_launch = 0
                self.recompiles.clear()

    def done(self, step: int = 0) -> bool:
        """The first call whose result the host waited for is back: the
        launch is over.  True the first time of a launch."""
        with self._lock:
            if self._done is not None:
                return False
            self.step = int(step)
            self._done = (time.perf_counter(), int(step))
            return True

    # -- the reduction --
    def summary(self, t0: Optional[float] = None, t1: Optional[float] = None,
                top: Optional[int] = None) -> dict:
        """Partition the launch thread's time in ``[t0, t1]`` (default: the
        launch's start to ``done``, or to now) by the innermost span over
        each instant, own seconds by ``_own_seconds``: the nine
        ``LAUNCH_PARTS``, which add up to ``seconds``.  ``cache_load_s`` is
        the backend spans that ended in a cache hit (key, read,
        deserialize; jax's own count of the last two is ``retrieval_s``),
        ``compile_s`` the others.  Beside them ``programs`` (every thread's
        compiles in the interval, by program; the ``top`` slowest and one
        row for the rest, if given), ``spans`` (the launch thread's own
        seconds by span name) and the counts."""
        with self._lock:
            rows = [list(r) for r in self._rows]
            done = self._done
        t0 = self._t0 if t0 is None else t0
        if t1 is None:
            t1 = done[0] if done else self._written or time.perf_counter()
        by_thread: Dict[int, list] = defaultdict(list)
        for i, (_name, s, e, _parent, thread, _attrs) in enumerate(rows):
            s, e = max(s, t0), min(t1 if e is None else e, t1)
            if s <= e:
                by_thread[thread].append((i, s, e))
        notes = []
        main = by_thread[self.thread]
        root = [(_ROOT, t0, t1)]
        if not any(rows[i][0] == "backend" for i, _s, _e in main):
            # An entry point that is not the program's touched the backend
            # itself: between jax's import and `enable_compile_cache`.
            after = [e for i, _s, e in main if rows[i][0] == "import:jax"]
            mark = [s for i, s, _e in main if rows[i][0] == "compile_cache"
                    and after and s >= after[0]]
            if mark:
                root.append((_MARKS, after[0], mark[0]))
                notes.append(
                    "chip_start_s was read between two marks: the launch "
                    "thread's uncovered time from the end of import:jax to "
                    "enable_compile_cache()")
        if not any(rows[i][0] in _WAIT_SPANS for i, _s, _e in main):
            notes.append(
                "no apex:force_oldest span in the interval: the waits for "
                "the device are not the program's, they lie under "
                "unattributed_s")
        parts = dict.fromkeys(LAUNCH_PARTS, 0.0)
        spans: Dict[str, float] = defaultdict(float)
        programs: Dict[str, dict] = defaultdict(_program_row)
        caches = collections.Counter()
        for thread, events in by_thread.items():
            on_main = thread == self.thread
            for key, _start, own in _own_seconds(
                    (root if on_main else []) + events):
                if key == _ROOT:
                    parts["unattributed_s"] += own
                    continue
                if key == _MARKS:
                    parts["chip_start_s"] += own
                    continue
                name, _s, _e, _parent, _thread, attrs = rows[key]
                if on_main:
                    parts[_part(name, attrs)] += own
                    spans[name] += own
                if name in _COMPILE_SPANS.values():
                    p = programs[attrs["program"]]
                    p[name.split(":")[1] + "_s"] += own
                    if name == "compile:backend":
                        p["compiles"] += 1
                        p["cache"].add(attrs["cache"])
                        p["retrieval_s"] += attrs.get("retrieval_s", 0.0)
                        caches[attrs["cache"]] += 1
        ranked = sorted(programs, key=lambda k: -(
            programs[k]["trace_s"] + programs[k]["lower_s"]
            + programs[k]["backend_s"]))
        if top is not None and len(ranked) > top:
            rest = programs[f"({len(ranked) - top} other programs)"]
            for k in ranked[top:]:
                for f, v in programs[k].items():
                    rest[f] = rest[f] | v if f == "cache" else rest[f] + v
            ranked = ranked[:top] + [f"({len(ranked) - top} other programs)"]
        return {
            "pid": self.pid,
            "seconds": t1 - t0,
            "done": done is not None,
            "step": done[1] if done else None,
            **parts,
            "cache_hits": caches["hit"],
            "cache_misses": caches["miss"],
            "compiles_after_launch": self.compiles_after_launch,
            "programs": {k: {
                f: ("+".join(sorted(v)) or None) if f == "cache"
                else round(v, 6) if isinstance(v, float) else v
                for f, v in programs[k].items()} for k in ranked},
            "spans": {k: round(v, 6) for k, v in
                      sorted(spans.items(), key=lambda kv: -kv[1])},
            "dropped": self.dropped,
            "folded": self.folded,
            "notes": notes,
        }

    def since_launch(self) -> dict:
        """The compiles after ``done``: how many, and the latest by program,
        learner step and seconds."""
        with self._lock:
            return {"compiles_after_launch": self.compiles_after_launch,
                    "recompiles": list(self.recompiles)}

    def varz(self) -> dict:
        """The obs provider ``launch``: the summary (twelve programs) and
        the recompiles since."""
        return {**self.summary(top=12), **self.since_launch()}

    # -- a process whose end the program does not own --
    def write(self, path: str) -> None:
        """The whole log as one JSON object (``from_file`` reads it)."""
        with self._lock:
            doc = {"pid": self.pid, "created": list(self.created),
                   "thread": self.thread, "t0": self._t0,
                   "done": list(self._done) if self._done else None,
                   "written": time.perf_counter(), "dropped": self.dropped,
                   "folded": self.folded,
                   "compiles_after_launch": self.compiles_after_launch,
                   "recompiles": list(self.recompiles),
                   "spans": [list(r) for r in self._rows]}
        tmp = f"{path}.{os.getpid()}.tmp"
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)

    @classmethod
    def from_file(cls, path: str) -> "LaunchLog":
        with open(path) as f:
            doc = json.load(f)
        log = cls()
        log.pid, log.created = doc["pid"], tuple(doc["created"])
        log.thread, log._t0, log._written = (
            doc["thread"], doc["t0"], doc["written"])
        log._done = tuple(doc["done"]) if doc["done"] else None
        log.dropped, log.folded = doc["dropped"], doc["folded"]
        log.compiles_after_launch = doc["compiles_after_launch"]
        log.recompiles.extend(doc["recompiles"])
        # a span still open when the process ended lasts until the writing
        log._rows = [[n, s, doc["written"] if e is None else e, p, t, a]
                     for n, s, e, p, t, a in doc["spans"]]
        return log


launch = LaunchLog()


def launch_span(name: str):
    """Decorator: every call of the function under ``launch.span(name)``
    (the builders'; looked up at the call, so a test may swap the log)."""
    def wrap(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with launch.span(name):
                return fn(*args, **kwargs)
        return wrapped
    return wrap


# -- what feeds the log from outside its callers: imports and jax's compiles --

class _TimedLoader:
    """A module's own loader, its ``exec_module`` under an ``import:<name>``
    span; the module keeps the real loader afterwards."""

    def __init__(self, loader):
        self._loader = loader

    def __getattr__(self, name):
        return getattr(self._loader, name)

    def exec_module(self, module):
        spec = getattr(module, "__spec__", None)
        try:
            with launch.span("import:" + module.__name__):
                self._loader.exec_module(module)
        finally:
            if spec is not None and spec.loader is self:
                spec.loader = self._loader
            if getattr(module, "__loader__", None) is self:
                module.__loader__ = self._loader


class _ImportTimer:
    """The finder at the front of ``sys.meta_path``: for a name of
    ``TIMED_IMPORTS`` it hands back the spec the other finders find, with
    the loader timed.  The import system asks a finder only on a miss in
    ``sys.modules``, so a module is timed once and nothing is added to any
    later import of it; what the others cannot find, or fail to run, raises
    as it would without this."""

    def __init__(self, names=TIMED_IMPORTS):
        self.names = names

    def find_spec(self, fullname, path=None, target=None):
        if fullname not in self.names:
            return None
        for finder in sys.meta_path:
            find = getattr(finder, "find_spec", None)
            spec = None if finder is self or find is None else find(
                fullname, path, target)
            if spec is not None:
                if hasattr(spec.loader, "exec_module"):
                    spec.loader = _TimedLoader(spec.loader)
                return spec
        return None


_installed = False


def install() -> None:
    """What ``ape_x_dqn_tpu/__init__.py`` runs once a process: the import
    timer goes to the front of ``sys.meta_path``, and with
    ``APEX_LAUNCH_LOG`` set the log is written there when the process ends
    (``{pid}`` in the path is this process's id: children that inherit the
    variable then write files of their own)."""
    global _installed
    if _installed:
        return
    _installed = True
    sys.meta_path.insert(0, _ImportTimer())
    atexit.register(_write_at_exit)


def _write_at_exit() -> None:
    path = os.environ.get(LAUNCH_LOG_ENV)
    if path:
        launch.write(path.replace("{pid}", str(os.getpid())))


def _on_time_span(event, start, end, **kw) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is not None:
        launch._compile_span(name, start, end, kw.get("fun_name", "?"))


def _on_event(event, **_kw) -> None:
    cache = _CACHE_EVENTS.get(event)
    if cache is not None:
        launch._cache_note(cache=cache)


def _on_duration(event, duration, **_kw) -> None:
    if event == _CACHE_RETRIEVAL:
        launch._cache_note(retrieval_s=round(float(duration), 6))


_listening = False


def listen() -> None:
    """Register, once a process, the listeners that turn jax's compile
    events into spans of ``launch`` (``enable_compile_cache`` calls this).
    They fire once per compile, never per call."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring

    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
