"""``check_flag_control.py`` with the Kanana-2 reference's three flags:
``reference_drops_shared_key`` (the scores without ``qR . kR``: a kernel that
lost its second operand), ``reference_skips_latent_norm`` (``c' = c``: a mixer
that lost ``kv_a_layernorm``) and ``reference_unscaled_gates`` (the gates
without ``routed_scaling_factor``).  That file names one flag and may not be
edited by the PR that adds a cell; here its ``FLAGS`` are rebound and
``check_delta_controls.py``'s ``main`` runs, as ``check_latent_controls.py``
does for Ling's flags: the driver's readings with each row's priority in the
program less the reference's, and, a row, the gap of the reference's two
largest online Q values at ``next_obs`` with what the target network would add
were the second taken (a row whose gap is inside bfloat16's rounding of Q and
whose difference is that addition had its double-Q argmax flipped).

    python3 benchmark/check_kanana_controls.py --config kanana2_q_ep8 --seeds 1 \
        --control-seeds 1 --first-seed 4300100003 \
        --controls bf16_held,reference_drops_shared_key,reference_skips_latent_norm

A seed a process and at most three controls at 624 M parameters, as
``check_delta_controls.py`` and for its reason (the chip machine's 40 GiB of
host memory).
"""

import sys

import check_delta_controls
import check_flag_control

FLAGS = ("reference_drops_shared_key", "reference_skips_latent_norm", "reference_unscaled_gates")


def main(argv=None) -> int:
    """``check_delta_controls.main`` (the driver's ``main`` with each row's
    difference printed) under this reference's flags."""
    check_flag_control.FLAGS = FLAGS
    return check_delta_controls.main(argv)


if __name__ == "__main__":
    sys.exit(main())
