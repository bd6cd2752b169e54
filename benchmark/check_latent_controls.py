"""``check_flag_control.py`` with the Ling-3.0 reference's four flags:
``reference_ungrouped_router`` (the top 8 of all 512 outputs: a router that
forgot its groups), ``reference_drops_shared_key`` (the scores without ``qR .
kR``: a kernel that lost its second operand), ``reference_unbounded_gate``
(``-exp(A_log) softplus(.)`` in place of the bounded gate) and
``reference_resets_state`` (the state set to zero every chunk: a scan that
lost its carry).  That file names one flag and may not be edited by the PR
that adds a cell; here its ``FLAGS`` are rebound and its ``main`` runs, with
``check_delta_controls.py``'s one line more a call: each row's priority in the
program less the reference's, beside the reference's.  The driver prints
their ratio and, a row, the gap of the reference's two largest online Q
values at ``next_obs`` with what the target network would add were the second
taken; a row whose gap is inside bfloat16's rounding of Q and whose
difference printed here is that addition had its double-Q argmax flipped.

    python3 benchmark/check_latent_controls.py --config ling3_q_l7 --seeds 1 \
        --control-seeds 1 --first-seed 4200100003 \
        --controls bf16_held,reference_ungrouped_router,reference_drops_shared_key

A seed a process and at most three controls at 763 M parameters, as
``check_delta_controls.py`` and for its reason (the chip machine's 40 GiB of
host memory).
"""

import sys

import check_delta_controls
import check_flag_control

FLAGS = ("reference_ungrouped_router", "reference_drops_shared_key", "reference_unbounded_gate",
         "reference_resets_state")


def main(argv=None) -> int:
    """``check_delta_controls.main`` (the driver's ``main`` with each row's
    difference printed) under this reference's flags."""
    check_flag_control.FLAGS = FLAGS
    return check_delta_controls.main(argv)


if __name__ == "__main__":
    sys.exit(main())
