"""Device time per pass of the learner's step, from a traced run: the six
``pass.*_step_us`` metrics.

A third axis beside stages (``stage_times.py``) and parts (``torso_times.py``):
what ``learner.forward_us_per_step`` and ``learner.backward_us_per_step`` lump
together, read apart.  The program names two scopes by hand,
``jax.named_scope("pass:bootstrap")`` around the forwards on ``next_obs`` and
``pass:again`` around the forward that a hand-written backward computes a
second time; AD writes the rest: ``rematted_computation`` is jax's own
name-stack segment for what a ``jax.checkpoint`` computes again, ``transpose(``
its mark of a pull-back.  An ``op_name`` whose stage (``stage_times``' rule) is
``forward`` or ``backward`` names one of four passes:

  * ``bootstrap``: it holds ``pass:bootstrap``;
  * ``forward``: stage ``forward`` otherwise (the differentiated forward, the
    loss, the casts);
  * ``recompute``: stage ``backward`` and ``rematted_computation`` or
    ``pass:again`` is a segment of it (the pull-back of what ran under the
    scope reads ``transpose(pass:again)`` and is not; nested recomputation is
    ``recompute`` once);
  * ``backward``: stage ``backward`` otherwise (cotangents, collectives, the
    optimizer's update where the compiler fused it into a weight gradient).

Only instructions whose stage, by ``stage_times.instruction_stages`` and in
the text ``stage_times.stage_seconds`` chooses, is ``forward`` or ``backward``
are counted, each in one pass, so the four add up to
``learner.forward_us_per_step`` + ``learner.backward_us_per_step`` of the same
run, exactly.  An instruction with no pass of its own takes the pass its
consumers in the same computation agree on (through further unscoped ones),
else its producers', nothing through a loop, a branch or an instruction of
another stage; with neither, the first pass of its stage (``forward``,
``backward``).  An event is one instruction: a fusion that holds several
passes is credited whole to the one its own metadata names, and the share of
time in such fusions is printed, with the share in fusions that hold both
passes of one stage (a forward op cloned into a backward fusion blurs the
stages' split, which ``stage_times`` prints, not this one).  A kernel that recomputes inside itself
(``blocked_attention``'s dq and dk/dv, ``scan_layout``'s) is one instruction
of pass ``backward``.

The two walk metrics are the same times over the instructions whose part
(``torso_times.instruction_parts``) is a recurrent walk's, ``ssm_scan`` or
``delta_scan``: a walk's forward share is its part's ``*_step_us`` less the two.

A program whose text holds no ``pass:`` (the parent of the PR that added the
scopes) gives no table and every reader returns nothing; one that does gives
all six a number, 0.0 where the network recomputes nothing or walks nothing.
"""

from __future__ import annotations

import re

import stage_times
import torso_times

PASSES = ("bootstrap", "forward", "recompute", "backward")
WALKS = ("ssm_scan", "delta_scan")
PREFIX = "pass:"
COUNTED = ("forward", "backward")   # the stages the passes divide; each names its first pass
SPLITS = ({"bootstrap", "forward"}, {"recompute", "backward"})   # a stage's two passes
_AGAIN = re.compile(r"(?:^|/)(?:rematted_computation|pass:again)(?:/|$)")
_COMPUTATION = stage_times._COMPUTATION
_INSTRUCTION = stage_times._INSTRUCTION
_OP_NAME = stage_times._OP_NAME
_NAME = stage_times._NAME
_CALLS = stage_times._CALLS
_CONTROL_FLOW = stage_times._CONTROL_FLOW


def scope_pass(op_name: str):
    """The pass an ``op_name`` puts its instruction in, or None where its
    stage is neither ``forward`` nor ``backward``."""
    stage = stage_times.scope_stage(op_name)
    if stage not in COUNTED:
        return None
    if PREFIX + "bootstrap" in op_name:
        return "bootstrap"
    if stage == "forward":
        return "forward"
    return "recompute" if _AGAIN.search(op_name) else "backward"


def instruction_passes(hlo_text: str, stages: dict) -> tuple:
    """({instruction name: pass, for the instructions that ``stages`` puts in
    ``forward`` or ``backward``}, {name of a fusion that holds instructions of
    more than one pass: those passes}) for the module."""
    own, comp_of, operands, users, order, barriers = {}, {}, {}, {}, [], set()
    inner: dict = {}  # computation -> passes of its scoped instructions
    calls: dict = {}  # fusion instruction -> computation it calls
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m and comp is not None and m.group(1) not in own:
            name, rest = m.group(1), line[m.end():]
            op = _OP_NAME.search(rest)
            own[name] = scope_pass(op.group(1)) if op else None
            if own[name] is not None:
                inner.setdefault(comp, set()).add(own[name])
            # an instruction of another stage hands no pass on to its neighbours
            elif _CONTROL_FLOW.search(rest) or (op and "stage:" in op.group(1)):
                barriers.add(name)
            called = _CALLS.search(rest)
            if called:
                calls[name] = called.group(1)
            comp_of[name], operands[name] = comp, rest
            order.append(name)
    for name in order:
        operands[name] = [o for o in set(_NAME.findall(operands[name]))
                          if comp_of.get(o) == comp_of[name] and o != name]
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)
    down, up = {}, {}
    for name in reversed(order):
        down[name] = ({own[name]} if own[name] is not None else set() if name in barriers else
                      set().union(*(down.get(u, ()) for u in users.get(name, ()))))
    for name in order:
        up[name] = ({own[name]} if own[name] is not None else set() if name in barriers else
                    set().union(*(up.get(o, ()) for o in operands[name])))

    def settle(found):
        return next(iter(found)) if len(found) == 1 else None

    passes = {name: settle(down[name]) or (not down[name] and settle(up[name]))
              or stages[name]
              for name in order if stages.get(name) in COUNTED}
    mixed = {name: inner[c] for name, c in calls.items() if len(inner.get(c, ())) > 1}
    return passes, mixed


def table(r):
    """{pass: microseconds per learner step} for the four passes and, under
    ``walk_recompute`` and ``walk_backward``, the walks' share of two of them;
    None where the program names no pass or the trace holds fewer than two
    whole runs.  Computed once, kept on ``r``."""
    if not hasattr(r, "_pass_table"):
        r._pass_table = None
        ops, _fused_s, _other_s, runs = stage_times.op_seconds(
            r.trace, r.fused_program, r.trace_reduce)
        total = sum(ops.values())
        # the text ``stage_times.stage_seconds`` chooses: the sums are its sums
        best, best_stages, best_named = None, {}, -1.0
        for text in stage_times.program_texts(r.fused_program):
            stages, _mixed = stage_times.instruction_stages(text)
            named = sum(s for name, s in ops.items() if name in stages)
            if named > best_named:
                best, best_stages, best_named = text, stages, named
            if named >= 0.95 * total:
                break
        if best is not None and PREFIX in best and runs > 1 and best_named > 0:
            passes, mixed = instruction_passes(best, best_stages)
            parts = torso_times.instruction_parts(best)
            secs = dict.fromkeys(PASSES + ("walk_recompute", "walk_backward"), 0.0)
            for name, s in ops.items():
                found = passes.get(name)
                if found is not None:
                    secs[found] += s
                    if parts.get(name) in WALKS and "walk_" + found in secs:
                        secs["walk_" + found] += s
            per_step = 1e6 / (runs * r.config["steps_per_call"])
            r._pass_table = {k: v * per_step for k, v in secs.items()}
            counted = max(sum(secs[p] for p in PASSES), 1e-30)
            in_mixed = sum(s for name, s in ops.items() if name in mixed and name in passes)
            # a cheap forward op cloned into a backward fusion moves neither split; what
            # blurs this one is a fusion whose passes divide one stage
            in_split = dict.fromkeys(PASSES, 0.0)     # by the pass such a fusion is credited to
            for name, s in ops.items():
                if name in passes and any(pair <= mixed.get(name, set()) for pair in SPLITS):
                    in_split[passes[name]] += s
            print(f"[bench] passes: {counted / total * 100:.2f}% of the fused program's op time is "
                  f"forward or backward, {in_mixed / counted * 100:.2f}% of that in fusions that "
                  f"hold more than one pass, {sum(in_split.values()) / counted * 100:.2f}% in "
                  f"fusions that hold both passes of one stage (credited to "
                  + ", ".join(f"{p} {in_split[p] / counted * 100:.2f}%" for p in PASSES) + ")",
                  flush=True)
    return r._pass_table


def read(r, name: str):
    """A pass's (or a walk share's) microseconds per step, or None."""
    t = table(r)
    return None if t is None else t[name]
