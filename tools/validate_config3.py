"""Validate config3 (paper-scale Ape-X: 2M-slot replay, dp=4) end to end —
"a driver that neither OOMs nor starves".

Loads configs/config3_seaquest_256actors_2m.json VERBATIM, then applies
only the deviations a small CPU host forces (each recorded in the output
record):

  * env -> fake-atari (ALE not installed; same 84x84 uint8 frames),
  * 8 thread actors instead of 256 process actors (1 host core),
  * steps_per_call 8 / min_replay 4096 / total 64 steps (CPU-speed),

while keeping what the validation is FOR at full scale: the 2M-transition
frame-dedup ring with frame_ratio 1.25 (17.9 GB of stored rows), sharded over a
data_parallel=4 virtual mesh, ingested from live dedup-emitting actors and
trained by the sharded fused K-step scan.  Asserts the run completes, the
loss is finite, ingest kept up (no shard starved below the warmup bar),
and reports the measured ring bytes vs the double-store equivalent.

The platform comes from the environment, like every other entry point, and
the record names it.  On the CPU it needs four virtual devices:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python tools/validate_config3.py

On a four-chip host, run it with no platform variable at all.
"""

from __future__ import annotations

import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main() -> int:
    from ape_x_dqn_tpu.config import load_config
    from ape_x_dqn_tpu.parallel.mesh import device_info
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    device = device_info()
    if device["device_count"] < 4:
        raise SystemExit(
            f"validate_config3: data_parallel=4 needs 4 devices, jax found "
            f"{device} (on the CPU: XLA_FLAGS="
            "--xla_force_host_platform_device_count=4)"
        )
    cfg = load_config(
        os.path.join(os.path.dirname(__file__), "..",
                     "configs", "config3_seaquest_256actors_2m.json")
    )
    deviations = {}

    def dev(path, value, why):
        section, field = path.split(".")
        deviations[path] = {
            "config3": getattr(getattr(cfg, section), field),
            "validation": value, "why": why,
        }
        setattr(getattr(cfg, section), field, value)

    dev("env.name", "fake-atari", "ALE not installed in this image")
    dev("actor.num_actors", 8, "small host (256 process actors need a real fleet host)")
    dev("actor.mode", "thread", "small host")
    dev("learner.steps_per_call", 8, "CPU-mesh speed")
    dev("learner.ingest_block", 512, "scaled with steps_per_call")
    dev("learner.min_replay_mem_size", 4096, "CPU-mesh fill speed")
    dev("learner.total_steps", 64, "validation run length")
    # NOT deviated - the point of the validation:
    kept = {
        "replay.capacity": cfg.replay.capacity,
        "replay.dedup": cfg.replay.dedup,
        "replay.frame_ratio": cfg.replay.frame_ratio,
        "learner.data_parallel": cfg.learner.data_parallel,
        "learner.device_replay": cfg.learner.device_replay,
        "learner.sample_ahead": cfg.learner.sample_ahead,
        "network": cfg.network,
    }
    assert cfg.replay.capacity == 2_000_000 and cfg.learner.data_parallel == 4

    t0 = time.time()
    pipe = AsyncPipeline(
        cfg, logger=MetricLogger(stream=open(os.devnull, "w")),
        log_every=10**9,
    )
    ring = pipe.fused._replay
    frame_bytes = int(ring.rows.nbytes)
    double_store_bytes = 2 * cfg.replay.capacity * int(
        np.prod(ring.fmt.obs_shape)
    )
    result = pipe.run(learner_steps=64, warmup_timeout=3600.0)
    wall = time.time() - t0
    rec = {
        "config": "config3_seaquest_256actors_2m.json",
        "device": device,
        "kept_at_scale": kept,
        "deviations": deviations,
        "learner_steps": result["step"],
        "actor_steps": result["actor_steps"],
        "loss": result["learner/loss"],
        "ingested_transitions": pipe.fused.size,
        "staged_backlog": pipe.fused.staged_rows,
        "dropped_carry": pipe.fused._stager.dropped_carry,
        "ring_frame_bytes": frame_bytes,
        "ring_frame_gb": round(frame_bytes / 1e9, 2),
        "double_store_equivalent_gb": round(double_store_bytes / 1e9, 2),
        "per_chip_gb_at_dp4": round(frame_bytes / 4 / 1e9, 2),
        "peak_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2
        ),
        "wall_s": round(wall, 1),
        "passed": bool(
            result["step"] >= 64
            and np.isfinite(result["learner/loss"])
            and pipe.fused.size >= cfg.learner.min_replay_mem_size
        ),
    }
    print(json.dumps(rec))
    out = os.path.join(os.path.dirname(__file__), "..",
                       "demos", "config3_validation.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    return 0 if rec["passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
