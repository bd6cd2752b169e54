"""Device time per stage of the fused learner, from a traced run.

The program wraps each stage of its fused learner in a
``jax.named_scope("stage:<name>")``; the scope of an operation survives
compilation only as ``op_name="..."`` metadata in the executable's HLO text,
and a device trace names an event by its HLO instruction alone.  So the map
instruction -> stage is made here from the text the program hands out
(``ape_x_dqn_tpu.utils.profiling.fused_hlo_texts``; a program without it
gives no table and every reader of one returns nothing), and the times come
from the trace as ``fused.us_per_step`` takes them: ops inside the whole runs
of the fused program in ``span_window``, each op's own time (a ``while`` less
its body), averaged over chips, over runs x K.  The other programs (the
dedup layouts' ingest programs, the key split) are taken per period between
fused runs, on the device's clock.

An instruction with no scope of its own (the compiler's layout copies,
``copy-start``/``copy-done``, bitcasts) takes the stage of the instructions
that consume it when they agree on one (forward and backward together count
as forward: the backward pass re-reads what the forward pass made), or,
where no consumer has a stage, of those that produce it; else it is
``other``, and so is an event the text does not hold and the time inside a
run with no op running.  An event is one instruction: a fusion that holds
ops of several stages (the compiler fuses the optimizer's update into the
backward pass's weight-gradient fusions) is credited whole to the stage its
own metadata names, and the share of time in such fusions is printed.  The
eight sums therefore add up to the fused program's time per step plus the
other programs' (the ingest programs' and the key split's), exactly.

This rule lives here alone.  The program's own summary (``/varz?trace=1``,
``profiling.hlo_stages``) reads an instruction's own scope and calls the rest
``other``; ``tests/benchmark`` holds the two to one answer on every scoped
instruction.
"""

from __future__ import annotations

import re
import time

OTHER = "other"
# The stages the seven named metrics read between them (layer_metrics/).
READ_BY_NAME = ("ingest", "sample", "gather", "restamp", "forward", "backward",
                "optimizer", "target_sync")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"stage:(\w+)")
_BACKWARD = re.compile(r"transpose\([^/]*stage:forward")
_NAME = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_CONTROL_FLOW = re.compile(r"\b(?:body|branch_computations|true_computation)=")


def scope_stage(op_name: str):
    """The stage an ``op_name`` puts its instruction in, or None: the
    innermost ``stage:<name>``; ``forward`` under ``transpose(`` is
    ``backward``."""
    found = _SCOPE.findall(op_name)
    if not found:
        return None
    if found[-1] == "forward" and _BACKWARD.search(op_name):
        return "backward"
    return found[-1]


def _settle(found: set):
    """The one stage a set of neighbours agrees on, or None.  The backward
    pass consumes what the forward pass made, so the two together are the
    forward's."""
    if found == {"forward", "backward"}:
        return "forward"
    return next(iter(found)) if len(found) == 1 else None


def instruction_stages(hlo_text: str) -> tuple:
    """({instruction name: stage}, {names of fusions that hold instructions
    of more than one stage}) for the module.  An instruction with no scope
    takes the stage its consumers in the same computation agree on (through
    further unscoped ones); where no consumer has any, the stage its
    producers agree on; else ``other``.  A ``while`` or a conditional hands
    nothing on in either direction, and its own time is ``other``."""
    own, comp_of, operands, users = {}, {}, {}, {}
    comp, order, barriers = None, [], set()
    inner: dict = {}  # computation -> stages of its scoped instructions
    calls: dict = {}  # fusion instruction -> computation it calls
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m and comp is not None and m.group(1) not in own:  # names are the module's
            name, rest = m.group(1), line[m.end():]
            op = _OP_NAME.search(rest)
            own[name] = scope_stage(op.group(1)) if op else None
            if own[name] is not None:
                inner.setdefault(comp, set()).add(own[name])
            comp_of[name] = comp
            operands[name] = rest
            called = _CALLS.search(rest)
            if called:
                calls[name] = called.group(1)
            if own[name] is None and _CONTROL_FLOW.search(rest):
                barriers.add(name)
            order.append(name)
    for name in order:
        operands[name] = [o for o in set(_NAME.findall(operands[name]))
                          if comp_of.get(o) == comp_of[name] and o != name]
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)

    # The text lists a computation's instructions operands first: in reverse
    # every consumer is settled before what it consumes, forwards every
    # producer before what it produces.
    # A loop or branch is no consumer of what goes into it, nor producer of
    # what comes out: nothing is inherited through one.
    down, up = {}, {}  # stages of the nearest scoped instructions down- and upstream
    for name in reversed(order):
        down[name] = ({own[name]} if own[name] is not None else set() if name in barriers else
                      set().union(*(down.get(u, ()) for u in users.get(name, ()))))
    for name in order:
        up[name] = ({own[name]} if own[name] is not None else set() if name in barriers else
                    set().union(*(up.get(o, ()) for o in operands[name])))
    stages = {name: _settle(down[name]) or (not down[name] and _settle(up[name])) or OTHER
              for name in order}
    mixed = {name for name, c in calls.items() if len(inner.get(c, ())) > 1}
    return stages, mixed


def op_seconds(trace, program: str, trace_reduce) -> tuple:
    """({instruction: own seconds inside the fused program's whole runs},
    seconds of those runs, seconds of every other program beside them, runs),
    averaged over chips, for the whole runs of ``program`` in the window.

    The other programs are taken per period, on the device's clock: what
    they run between the end of the first whole fused run and the end of the
    last, over the periods between (then times ``runs``, so that it divides
    like the rest).  Whole runs in the host's window would leave one out:
    the host sees a call complete after the device has begun the next call's
    ingest."""
    t0, t1 = trace_reduce.span_window(trace)
    n = len(trace.devices)
    ops: dict = {}
    fused_s = other_s = 0.0
    runs = 0
    for dev in trace.devices.values():
        mine = sorted((e.start, e.end) for e in dev.modules
                      if e.start >= t0 and e.end <= t1 and e.name.split("(", 1)[0] == program)
        runs = max(runs, len(mine))
        fused_s += sum(e - s for s, e in mine) / n
        if len(mine) > 1:
            p0, p1 = mine[0][1], mine[-1][1]
            beside = sum(max(0.0, min(e.end, p1) - max(e.start, p0)) for e in dev.modules
                         if e.name.split("(", 1)[0] != program)
            other_s += beside / (len(mine) - 1) * len(mine) / n
        inside = [e for e in dev.ops
                  if any(s <= e.start and e.end <= t for s, t in mine)]
        for name, secs in trace_reduce.self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + secs / n
    return ops, fused_s, other_s, runs


def stage_seconds(ops: dict, fused_s: float, hlo_texts) -> tuple:
    """({stage: seconds}, share of the ops' time whose instruction the chosen
    text holds, share of it in fusions that hold more than one stage and are
    credited whole to the one their own metadata names).  A process may have
    built the program more than once (the window's, then the comparison's):
    the texts are taken in turn until one names nearly all of the traced
    time, and the one that names most is used."""
    total = sum(ops.values())
    best, best_mixed, best_named = {}, set(), -1.0
    for text in hlo_texts:
        stages, mixed = instruction_stages(text)
        named = sum(s for name, s in ops.items() if name in stages)
        if named > best_named:
            best, best_mixed, best_named = stages, mixed, named
        if named >= 0.95 * total:
            break
    out: dict = {}
    for name, secs in ops.items():
        stage = best.get(name, OTHER)
        out[stage] = out.get(stage, 0.0) + secs
    # What no op accounts for inside a run is the fused program's too.
    out[OTHER] = out.get(OTHER, 0.0) + max(0.0, fused_s - total)
    if total <= 0:
        return out, 0.0, 0.0
    return out, best_named / total, sum(s for n, s in ops.items() if n in best_mixed) / total


def program_texts(program: str):
    """The program's HLO texts of ``program``, each made when reached, or
    nothing from a program that keeps none."""
    try:
        from ape_x_dqn_tpu.utils import profiling

        return profiling.fused_hlo_texts(program)
    except (ImportError, AttributeError):
        return ()


def table(r):
    """{stage: microseconds per learner step} of this run, ``ingest``
    including the other programs' runs; None where the program names no
    stage or the trace holds fewer than two whole runs.  Computed once, kept
    on ``r``."""
    if not hasattr(r, "_stage_table"):
        r._stage_table = None
        ops, fused_s, other_s, runs = op_seconds(r.trace, r.fused_program, r.trace_reduce)
        t0 = time.perf_counter()
        secs, named, mixed = stage_seconds(ops, fused_s, program_texts(r.fused_program))
        if runs > 1 and named > 0:
            secs["ingest"] = secs.get("ingest", 0.0) + other_s
            per_step = 1e6 / (runs * r.config["steps_per_call"])
            r._stage_table = {k: v * per_step for k, v in secs.items()}
            print(f"[bench] stages: {named * 100:.2f}% of the fused program's op time is on "
                  f"instructions its HLO text names ({time.perf_counter() - t0:.1f} s to get and "
                  f"read), {mixed * 100:.2f}% in fusions that hold more than one stage", flush=True)
    return r._stage_table


def read(r, *stages):
    """Sum of the named stages' microseconds per step, or None."""
    t = table(r)
    return None if t is None else sum(t.get(s, 0.0) for s in stages)


def read_rest(r):
    """What no stage metric reads: ``other`` and any stage not in
    ``READ_BY_NAME``."""
    t = table(r)
    return None if t is None else sum(v for k, v in t.items() if k not in READ_BY_NAME)
