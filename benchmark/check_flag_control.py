"""Readings for a control that is a flag of the reference's configuration, on
the chip, through ``drivers/learner_feed_collected.py``'s ``main``.

    python3 benchmark/check_flag_control.py --config granite4h_q_l10 --seeds 3 \
        --control-seeds 3 --controls reference_resets_state

The driver's ``CONTROLS`` name a precision or a shift of the gather, and the
driver may not be edited by the PR that adds a cell.  ``granite_h_q``'s control
of its own mechanism is a key of the configuration instead
(``cfg["reference_resets_state"]``: the state set to zero at every chunk
boundary, a scan that lost its carry), so here each name in ``FLAGS`` joins
``CONTROLS`` as a precision of that name, and ``reference_run`` turns it into
the stated precision under a configuration with the flag on.  The loop, the
seeds, the collections and what is printed (each row's priority over the
reference's, each row's bootstrap gap) are the driver's.  ``--controls``
keeps only the named controls.  Three seeds a process at 750 M parameters:
nine held 40 GiB of host memory (PERF.md, Open question 11).
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FLAGS = ("reference_resets_state",)


@contextlib.contextmanager
def flags_as_controls(base, keep=()):
    """``base`` (``drivers/learner_feed_by_name``) with every flag among its
    ``CONTROLS``, cut to the names in ``keep`` if any."""
    reference_run, controls = base.reference_run, base.CONTROLS

    def flagged(cfg, beta, inputs, shots, precision="stated", row_shift=0):
        if precision in FLAGS:
            cfg, precision = dict(cfg, **{precision: True}), "stated"
        return reference_run(cfg, beta, inputs, shots, precision, row_shift)

    named = {**controls, **{flag: (flag, 0) for flag in FLAGS}}
    base.reference_run, base.CONTROLS = flagged, {c: named[c] for c in keep or named}
    try:
        yield base
    finally:
        base.reference_run, base.CONTROLS = reference_run, controls


def main(argv=None) -> int:
    import manifest as mf

    ap = argparse.ArgumentParser()
    ap.add_argument("--controls", default="")
    args, rest = ap.parse_known_args(argv)
    drv = mf.load_module(os.path.join(HERE, "drivers", "learner_feed_collected.py"),
                         "bench_driver_learner_feed_collected")
    with flags_as_controls(drv.base, [c for c in args.controls.split(",") if c]):
        return drv.main(rest)


if __name__ == "__main__":
    sys.exit(main())
