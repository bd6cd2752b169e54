"""``check_flag_control.py`` with the Nemotron 3 Super reference's five flags:
``reference_shares_group0`` (every head reads group 0's ``B`` and ``C``: a scan
that lost its groups), ``reference_norms_all_channels`` (the gated norm's mean
square over all held channels in place of a group's),
``reference_silu_experts`` (``silu`` in place of ``relu^2`` in the experts,
routed and shared), ``reference_router_reads_latent`` (the router scoring the
latent in place of the layer's input) and ``reference_unscaled_gates`` (the
gates without ``routed_scaling_factor``).  That file names one flag and may not
be edited by the PR that adds a cell; here its ``FLAGS`` are rebound and
``check_delta_controls.py``'s ``main`` runs, as ``check_kanana_controls.py``
does for Kanana's flags: the driver's readings with each row's priority in the
program less the reference's, and, a row, the gap of the reference's two
largest online Q values at ``next_obs`` with what the target network would add
were the second taken (a row whose gap is inside bfloat16's rounding of Q and
whose difference is that addition had its double-Q argmax flipped).

    python3 benchmark/check_nemotron_controls.py --config nemotron3s_q_ep32 --seeds 1 \
        --control-seeds 1 --first-seed 4400100003 \
        --controls bf16_held,reference_shares_group0

A seed a process and at most two controls at 699 M parameters: a flag or a
precision is a reference program of its own, and with a third the process met
the chip machine's 40 GiB of host memory (my chip run, PR 59), where
``check_delta_controls.py``'s cells hold three.
"""

import sys

import check_delta_controls
import check_flag_control

FLAGS = ("reference_shares_group0", "reference_norms_all_channels", "reference_silu_experts",
         "reference_router_reads_latent", "reference_unscaled_gates")


def main(argv=None) -> int:
    """``check_delta_controls.main`` (the driver's ``main`` with each row's
    difference printed) under this reference's flags."""
    check_flag_control.FLAGS = FLAGS
    return check_delta_controls.main(argv)


if __name__ == "__main__":
    sys.exit(main())
