"""``check_flag_control.py`` with the Olmo-Hybrid reference's four flags:
``reference_pre_norm`` (the norms before the sublayers: every other torso's
block), ``reference_drops_decay`` (``g = 0``: the delta rule without its
gate), ``reference_beta_to_one`` (``beta = sigmoid(.)``: no negative
eigenvalue) and ``reference_norms_by_head`` (the full layer's queries and
keys normed a head at a time).  That file names one flag and may not be
edited by the PR that adds a cell; here its ``FLAGS`` are rebound and
``check_delta_controls.main`` runs (the collecting driver's ``main`` with
each row's priority in the program less the reference's printed beside the
reference's).  The driver prints their ratio and, a row, the gap of the
reference's two largest online Q values at ``next_obs`` with what the target
network would add were the second taken; a row whose gap is inside
bfloat16's rounding of Q and whose difference printed here is that addition
had its double-Q argmax flipped.

    python3 benchmark/check_gdn_controls.py --config olmoh_q_l4 --seeds 1 \
        --control-seeds 1 --first-seed 5100100003 \
        --controls bf16_held,reference_pre_norm,reference_drops_decay

A seed a process and at most three controls at 837 M parameters, as
``check_delta_controls.py`` and for its reason (the chip machine's 40 GiB of
host memory).
"""

import sys

import check_delta_controls
import check_flag_control

FLAGS = ("reference_pre_norm", "reference_drops_decay", "reference_beta_to_one",
         "reference_norms_by_head")


def main(argv=None) -> int:
    """``check_delta_controls.main`` under this reference's flags."""
    check_flag_control.FLAGS = FLAGS
    return check_delta_controls.main(argv)


if __name__ == "__main__":
    sys.exit(main())
