"""Device time per part of a torso of state-space and attention layers, from
a traced run: the five ``hybrid.*_step_us`` metrics, the two shares of a
roofline and the whole step's share of the peak.

``torso_times.py``'s reading (``instruction_parts``: an instruction's own
innermost ``torso:<name>``, else the part its consumers agree on, else its
producers') with ``blocks_times.kernel_parts``' rule for the attention
kernels (the scan is XLA's products and has no kernel).  Four parts are read
by name:
``ssm_scan`` (the chunking, the running sums and decays, the products inside
and across chunks; forward, recomputation and backward), ``attn_full`` (the
blocked attention kernels with their padding), ``mixer`` (norm, the input
projection, the convolution, the gated norm, the output projection; the
attention layer's projections), ``dense_ffn``.  ``hybrid.rest_step_us`` is
everything else of the fused program plus the other programs' time a step, so
the five add up to ``fused.us_per_step`` plus the other programs' time a
step, exactly.

A third table beside ``torso_times`` and ``blocks_times`` for the reason the
second exists: their lists of parts may not be edited by the PR that adds a
cell (PERF.md, Open question 10).  A program without such scopes gives no
table, and every reader returns nothing.
"""

from __future__ import annotations

import importlib

import blocks_times
import stage_times
import torso_times

READ_BY_NAME = ("ssm_scan", "mixer", "attn_full", "dense_ffn")


def table(r):
    """{part: microseconds per learner step} for the four parts read by name
    and ``rest``; None where the program names no scan or the trace holds
    fewer than two whole runs.  Kept on ``r``."""
    if not hasattr(r, "_hybrid_table"):
        r._hybrid_table = None
        ops, fused_s, other_s, runs = stage_times.op_seconds(
            r.trace, r.fused_program, r.trace_reduce)
        total = sum(ops.values())
        best, best_named = None, -1.0
        for text in stage_times.program_texts(r.fused_program):
            if "torso:ssm_scan" not in text:
                continue
            parts = torso_times.instruction_parts(text)
            parts.update(blocks_times.kernel_parts(text, parts))
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
            r._hybrid_table = {k: v * per_step for k, v in secs.items()}
            print(f"[bench] hybrid: {best_named / total * 100:.2f}% of the fused program's op "
                  f"time is on instructions its HLO text names", flush=True)
    return r._hybrid_table


def read(r, part: str):
    t = table(r)
    return None if t is None else t[part]


def _ops_count(r, needs: str):
    """The configuration's operation count if it has ``needs``, else None."""
    if "ops_count" not in r.config:
        return None
    ops = importlib.import_module(r.config["ops_count"])
    return ops if hasattr(ops, needs) else None


def roofline(r, part: str, floor: str, *args):
    """``<ops_count>.<floor>`` over the device time on ``part``, %."""
    us, ops = read(r, part), _ops_count(r, floor)
    if not us or ops is None:
        return None
    floor_s, _bound = getattr(ops, floor)(r.config, r.peaks, *args)
    return floor_s / (us * 1e-6) * 100.0


def mfu(r):
    """``<ops_count>.flops_per_sample`` from the shapes x the run's rate over
    the peak, %: the whole step's share; None for a configuration whose count
    needs routed pairs or that names no scan."""
    rate, ops = r.end_to_end.get("learn_samples_per_s"), _ops_count(r, "scan_floor_s")
    if rate is None or ops is None:
        return None
    chips = int(r.config.get("data_parallel", 1))
    return ops.flops_per_sample(r.config) * rate / (chips * r.peaks["flops_per_s_bf16"]) * 100.0
