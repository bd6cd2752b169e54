"""Device time per part of the network, from a traced run: the five
``torso.*_step_us`` metrics.

The program wraps the parts of its networks in
``jax.named_scope("torso:<name>")`` (``ape_x_dqn_tpu.utils.profiling.PARTS``:
stem, mixer, router, experts, dense_ffn, head), beside the ``stage:`` scopes
and not among them.  The times are ``stage_times.op_seconds``'s (the ops
inside the whole runs of the fused program in ``span_window``, each op's own
time; the other programs per period between fused runs) and the texts
``stage_times.program_texts``'s; the map instruction -> part is made here as
``stage_times`` makes instruction -> stage: an instruction's own innermost
``torso:<name>``, forward and backward alike (a part's backward pass and its
recomputation carry the part's scope under ``transpose(`` and
``checkpoint``); one with no scope takes the part its consumers in the same
computation agree on, else its producers', nothing through a loop or branch.
An event is one instruction: a fusion that holds several parts, or a part
and the optimizer's update of its weights, is credited whole to the part its
own metadata names.

Four parts are read by name (mixer, router, experts, dense_ffn);
``torso.rest_step_us`` is everything else of the fused program (stem,
head, loss, the replay stages, clip, optimizer, target sync, the loop's own
time) plus the other programs' time a step, so the five add up to
``fused.us_per_step`` plus the other programs' time a step, exactly.

A program without such scopes (the parent of the PR that added them) gives
no table, and every reader returns nothing.
"""

from __future__ import annotations

import re

import stage_times

READ_BY_NAME = ("mixer", "router", "experts", "dense_ffn")
_COMPUTATION = stage_times._COMPUTATION
_INSTRUCTION = stage_times._INSTRUCTION
_OP_NAME = stage_times._OP_NAME
_NAME = stage_times._NAME
_CONTROL_FLOW = stage_times._CONTROL_FLOW
_PART = re.compile(r"torso:(\w+)")


def instruction_parts(hlo_text: str) -> dict:
    """{instruction name: part or None} for the module."""
    own, comp_of, operands, users, order, barriers = {}, {}, {}, {}, [], set()
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
            found = _PART.findall(op.group(1)) if op else []
            own[name] = found[-1] if found else None
            # an instruction scoped by a stage and no part is the stage's:
            # it hands no part on to its unscoped neighbours
            if own[name] is None and (_CONTROL_FLOW.search(rest) or (op and "stage:" in op.group(1))):
                barriers.add(name)
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

    return {name: settle(down[name]) or (not down[name] and settle(up[name])) or None
            for name in order}


def table(r):
    """{part: microseconds per learner step} for the four parts read by name
    and ``rest``; None where the program names no part or the trace holds
    fewer than two whole runs.  Computed once, kept on ``r``."""
    if not hasattr(r, "_torso_table"):
        r._torso_table = None
        ops, fused_s, other_s, runs = stage_times.op_seconds(
            r.trace, r.fused_program, r.trace_reduce)
        total = sum(ops.values())
        best, best_named = None, -1.0
        for text in stage_times.program_texts(r.fused_program):
            if "torso:" not in text:
                continue
            parts = instruction_parts(text)
            named = sum(s for name, s in ops.items() if name in parts)
            if named > best_named:
                best, best_named = parts, named
            if named >= 0.95 * total:
                break
        if best is not None and runs > 1 and total > 0:
            secs = dict.fromkeys(READ_BY_NAME, 0.0)
            for name, s in ops.items():
                part = best.get(name)
                if part in secs:
                    secs[part] += s
            secs["rest"] = fused_s + other_s - sum(secs.values())
            per_step = 1e6 / (runs * r.config["steps_per_call"])
            r._torso_table = {k: v * per_step for k, v in secs.items()}
            print(f"[bench] parts: {best_named / total * 100:.2f}% of the fused program's op "
                  f"time is on instructions its HLO text names", flush=True)
    return r._torso_table


def read(r, part: str):
    t = table(r)
    return None if t is None else t[part]


def held_pairs_per_step(r):
    """Pairs on held experts a step: of the traced calls where the run
    counted them, else of the window's; None from a program that counts none."""
    c = r.counters
    return c.get("traced_held_pairs_per_step", c.get("held_pairs_per_step"))
