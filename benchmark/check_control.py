"""Readings for the limits: the program's numbers and the controls', on the
chip at a configuration's own widths and batch, over many seeds in one
process.

    python3 benchmark/check_control.py --config apex_b512 --seeds 12 --control-seeds 4

Prints one line per seed and, for each number compared, the two readings a
limit is set from: the largest the program gives and the smallest each
control gives.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# name -> (precision, rows the gather is off by)
CONTROLS = {
    "bf16_held": ("bf16_held", 0),
    "fp8_activations": ("fp8_activations", 0),
    "bf16_gradients": ("bf16_gradients", 0),
    "gather_one_row_on": ("stated", 1),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="learner_feed")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    args = ap.parse_args(argv)

    import jax

    import correctness
    import manifest as mf
    from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache

    if jax.default_backend() != "tpu":
        print(f"check_control.py: needs a TPU, jax's default backend is "
              f"{jax.default_backend()!r}; nothing read", file=sys.stderr)
        return 2
    enable_compile_cache()
    entry = [c for c in mf.load_manifest()["configs"] if c["name"] == args.config][0]
    cfg = mf.load_json(os.path.join(ROOT, entry["file"]))
    traffic = mf.load_json(os.path.join(HERE, "traffic", args.traffic + ".json"))
    driver = mf.load_module(os.path.join(HERE, "drivers", traffic["driver"] + ".py"),
                            "bench_driver_" + traffic["driver"])
    beta = float(traffic["beta"])
    dev = jax.devices()[0]
    print(f"config={args.config} device={dev.platform}:{dev.device_kind} x{len(jax.devices())}")
    sound, controls = [], {name: [] for name in CONTROLS}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        inputs, shots = driver.check_shots(cfg, traffic, seed)
        counts, got, reference = correctness.program_numbers(cfg, beta, inputs, shots)
        sound.append(got)
        print(f"seed {seed} program {json.dumps(got)} exact {json.dumps(counts)}", flush=True)
        if i < args.control_seeds:
            for name, (precision, shift) in CONTROLS.items():
                ctl = correctness.control_numbers(
                    cfg, beta, inputs, shots, reference, precision, shift)
                controls[name].append(ctl)
                print(f"seed {seed} control {name} {json.dumps(ctl)}", flush=True)
    for number in sound[0]:
        print(f"READING {args.config} {number}: program max "
              f"{max(s[number] for s in sound):.6g} over {len(sound)} seeds; " + "; ".join(
                  f"{name} min {min(c[number] for c in rows):.6g}"
                  for name, rows in controls.items() if rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
