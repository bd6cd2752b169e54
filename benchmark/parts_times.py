"""Device time per part of a torso of blocks, from a traced run, for any list
of parts: the table ``torso_times``, ``blocks_times`` and ``hybrid_times``
each write for their own list, written once.

The configuration names what is read: ``parts`` (the ``torso:<name>`` scopes
read by name, in the program's ``profiling.PARTS``), ``parts_scope`` (the
scope that marks the fused program's text among a run's programs) and
``parts_prefix`` (the metrics' prefix, ``<prefix>.<part>_step_us``; only the
files under ``layer_metrics/`` use it).  The reading is ``hybrid_times``':
``torso_times.instruction_parts`` (an instruction's own innermost
``torso:<name>``, else the part its consumers agree on, else its producers')
with ``blocks_times.kernel_parts``' rule for the attention kernels, over
``stage_times.op_seconds``'s times.  ``rest`` is everything else of the fused
program plus the other programs' time a step, so the parts and ``rest`` add
up to ``fused.us_per_step`` plus the other programs' time a step, exactly.

A program without the marking scope (the parent of the PR that added it), or
a configuration that names no parts, gives no table, and every reader
returns nothing.  The ``benchmark`` issue that joins the three older tables
(PERF.md, Open question 10) points their readers at this file.
"""

from __future__ import annotations

import importlib

import blocks_times
import stage_times
import torso_times


def table(r, parts=None, scope=None):
    """{part: microseconds per learner step} for ``parts`` (default the
    configuration's) and ``rest``; None where no part is named, the program
    lacks ``scope`` (default the configuration's ``parts_scope``) or the
    trace holds fewer than two whole runs.  The configuration's own table is
    kept on ``r``."""
    own = parts is None and scope is None
    if own and hasattr(r, "_parts_table"):
        return r._parts_table
    parts = tuple(r.config.get("parts", ()) if parts is None else parts)
    scope = r.config.get("parts_scope") if scope is None else scope
    out = None
    if parts and scope:
        ops, fused_s, other_s, runs = stage_times.op_seconds(
            r.trace, r.fused_program, r.trace_reduce)
        total = sum(ops.values())
        best, best_named = None, -1.0
        for text in stage_times.program_texts(r.fused_program):
            if scope not in text:
                continue
            named_parts = torso_times.instruction_parts(text)
            named_parts.update(blocks_times.kernel_parts(text, named_parts))
            named = sum(s for name, s in ops.items() if name in named_parts)
            if named > best_named:
                best, best_named = named_parts, named
            if named >= 0.95 * total:
                break
        if best is not None and runs > 1 and total > 0:
            secs = dict.fromkeys(parts, 0.0)
            for name, s in ops.items():
                part = best.get(name)
                if part in secs:
                    secs[part] += s
            secs["rest"] = fused_s + other_s - sum(secs.values())
            per_step = 1e6 / (runs * r.config["steps_per_call"])
            out = {k: v * per_step for k, v in secs.items()}
            print(f"[bench] parts ({', '.join(parts)}): {best_named / total * 100:.2f}% of the "
                  f"fused program's op time is on instructions its HLO text names", flush=True)
    if own:
        r._parts_table = out
    return out


def read(r, part: str):
    t = table(r)
    return None if t is None else t.get(part)


def roofline(r, part: str, floor: str, *args):
    """``<ops_count>.<floor>`` over the device time on ``part``, %."""
    us = read(r, part)
    if not us or "ops_count" not in r.config:
        return None
    ops = importlib.import_module(r.config["ops_count"])
    if not hasattr(ops, floor):
        return None
    floor_s, _bound = getattr(ops, floor)(r.config, r.peaks, *args)
    return floor_s / (us * 1e-6) * 100.0
