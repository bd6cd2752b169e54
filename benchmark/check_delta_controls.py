"""``check_flag_control.py`` with the delta-rule reference's two flags:
``reference_resets_state`` (the state set to zero every chunk: a scan that
lost its carry) and ``reference_drops_delta`` (the write without ``- beta k
k^T S``: plain gated linear attention).  That file names one flag and may not
be edited by the PR that adds a cell; here its ``FLAGS`` are rebound and its
``main`` runs, with one line more a call: each row's priority in the program
less the reference's, beside the reference's.  The driver prints their ratio
and, a row, the gap of the reference's two largest online Q values at
``next_obs`` with what the target network would add were the second taken; a
row whose gap is inside bfloat16's rounding and whose difference printed here
is that addition had its double-Q argmax flipped (PERF.md, section 6, PR 39:
seed 883846218).

    python3 benchmark/check_delta_controls.py --config solar2_q_ep40 --seeds 1 \
        --control-seeds 1 --controls reference_resets_state,reference_drops_delta,bf16_held

A seed a process and at most three controls at 709 M parameters: the process
holds 25-28 GiB of host memory after the program and the reference, each
control adds 1-3.5 GiB, and the chip machine ends a call at 40 (PERF.md,
section 6, PR 39).
"""

import argparse
import os
import sys

import check_flag_control

check_flag_control.FLAGS = ("reference_resets_state", "reference_drops_delta")


def with_differences(program_numbers):
    """The collecting driver's ``program_numbers``, then by call each row's
    ``program's priority - reference's (reference's)``."""

    def printed(cfg, beta, inputs, shots):
        counts, numbers, reference = program_numbers(cfg, beta, inputs, shots)
        for call, (got, want) in enumerate(zip(shots["priorities"], reference["priorities"])):
            print(f"[bench] check: call {call}, priorities less the reference's (the reference's): "
                  + " ".join(f"{g - w:+.4f} ({w:.4f})" for g, w in zip(got, want.reshape(-1))),
                  flush=True)
        return counts, numbers, reference

    return printed


def main(argv=None) -> int:
    import manifest as mf

    ap = argparse.ArgumentParser()
    ap.add_argument("--controls", default="")
    args, rest = ap.parse_known_args(argv)
    drv = mf.load_module(os.path.join(check_flag_control.HERE, "drivers",
                                      "learner_feed_collected.py"),
                         "bench_driver_learner_feed_collected")
    drv.program_numbers = with_differences(drv.program_numbers)
    keep = [c for c in args.controls.split(",") if c]
    with check_flag_control.flags_as_controls(drv.base, keep):
        return drv.main(rest)


if __name__ == "__main__":
    sys.exit(main())
