"""Device time per part of a torso of blocks with blocked attention, from a
traced run: the eight ``blocks.*_step_us`` metrics and the attention kernels'
shares of their roofline.

``torso_times.py``'s reading (``instruction_parts``: an instruction's own
innermost ``torso:<name>``, else the part its consumers agree on, else its
producers') with one rule more (``kernel_parts``): a Pallas call has no scope
of its own, and what consumes an attention kernel's output is fused by the
compiler into the mixer's next elementwise pass, so such a call takes the
``attn_*`` part of the paddings that produce its operands, which the
compiler leaves alone; over
``stage_times.op_seconds``'s times, with seven parts read by name: the two
attention kinds' kernels (``attn_window``, ``attn_full``: the masked products,
the softmax and their backward, with the padding to whole blocks), ``mixer``
(norm, projections, RoPE, head gate, ``W_o``), ``shared_expert``, ``router``,
``experts``, ``dense_ffn``.  ``blocks.rest_step_us`` is everything else of
the fused program plus the other programs' time a step, so the eight add up
to ``fused.us_per_step`` plus the other programs' time a step, exactly.

What reads the same on this cell as on ``lfm2moe_q_ep8``'s has no second
name: ``moe.*``, ``torso.mfu_pct`` and ``torso.experts_roofline`` list both
cells.  The times have one, because ``torso_times.READ_BY_NAME`` holds four
parts, knows no kernel rule and may not be edited here (PERF.md, Open
question 10).  A program without such scopes gives no table, and every
reader returns nothing.
"""

from __future__ import annotations

import re

import stage_times
import torso_times

READ_BY_NAME = ("attn_window", "attn_full", "mixer", "shared_expert", "router", "experts",
                "dense_ffn")


_KERNEL = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? custom-call\((.*?)\), custom_call_target=\"tpu_custom_call\"")


def kernel_parts(hlo_text: str, parts: dict) -> dict:
    """{Pallas call: ``attn_*`` part} for the calls one of whose operands
    carries exactly one such part."""
    out = {}
    for line in hlo_text.splitlines():
        m = _KERNEL.match(line)
        if m:
            found = {parts.get(o) for o in stage_times._NAME.findall(m.group(2))}
            found = {p for p in found if p and p.startswith("attn_")}
            if len(found) == 1:
                out[m.group(1)] = found.pop()
    return out


def table(r):
    """{part: microseconds per learner step} for the seven parts read by
    name and ``rest``; None where the program names none of the attention
    parts or the trace holds fewer than two whole runs.  Kept on ``r``."""
    if not hasattr(r, "_blocks_table"):
        r._blocks_table = None
        ops, fused_s, other_s, runs = stage_times.op_seconds(
            r.trace, r.fused_program, r.trace_reduce)
        total = sum(ops.values())
        best, best_named = None, -1.0
        for text in stage_times.program_texts(r.fused_program):
            if "torso:attn_" not in text:
                continue
            parts = torso_times.instruction_parts(text)
            parts.update(kernel_parts(text, parts))
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
            r._blocks_table = {k: v * per_step for k, v in secs.items()}
            print(f"[bench] blocks: {best_named / total * 100:.2f}% of the fused program's op "
                  f"time is on instructions its HLO text names", flush=True)
    return r._blocks_table


def read(r, part: str):
    t = table(r)
    return None if t is None else t[part]


def attention_roofline(r, kind: str):
    """``<ops_count>.attention_floor_s`` over the device time on the kernels
    of ``kind``, %."""
    import importlib

    us = read(r, "attn_" + kind)
    if not us or "ops_count" not in r.config:
        return None
    ops = importlib.import_module(r.config["ops_count"])
    if not hasattr(ops, "attention_floor_s"):
        return None
    floor_s, _bound = ops.attention_floor_s(r.config, r.peaks, kind)
    return floor_s / (us * 1e-6) * 100.0
