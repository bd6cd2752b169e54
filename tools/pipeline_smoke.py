"""Overlapped-dispatch pipeline smoke gate + bench (ISSUE 5).

Smoke (default; tools/verify_t1.sh gate 5): one short fused run on CPU
with the overlapped pipeline active (``learner.pipeline_depth`` > 1 +
``learner.sync_every``), asserting the two contracts the pipeline exists
to provide:

  1. **sync budget** — ``learner/host_syncs`` stays within
     ``steps / sync_every + slack``: the learner chained its dispatches
     instead of paying a blocking host read per call;
  2. **clean flush-at-exit** — every dispatched call was drained before
     the final record (``pipeline.inflight == 0``) and the final loss is
     finite (the drain actually forced the device work).

Bench (``--bench``; bench.py ``pipeline_overlap`` section): the same
workload swept over depth 1 (strict: one counted sync per fused call) /
2 / 4, reporting steps/s, host syncs per 1k steps, and the overlap-gap
(device idle between dispatches) percentiles.  A CPU run by
construction: it counts syncs and checks the accounting; it says nothing
about speed on a chip.

    python tools/pipeline_smoke.py
    python tools/pipeline_smoke.py --bench --steps 6400
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_point(depth: int, sync_every: int, steps: int,
              steps_per_call: int = 64, seed: int = 0) -> dict:
    """One fused AsyncPipeline run at (depth, sync_every); returns the
    point's throughput + sync/overlap accounting."""
    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "random:16x16x1"
    cfg.seed = seed
    cfg.actor.num_actors = 16
    cfg.actor.T = 10_000_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 64
    cfg.learner.device_replay = True
    cfg.learner.sample_ahead = True
    cfg.learner.steps_per_call = steps_per_call
    cfg.learner.ingest_block = 128
    cfg.learner.min_replay_mem_size = 512
    cfg.learner.publish_every = 4096
    cfg.learner.total_steps = steps
    cfg.learner.pipeline_depth = depth
    cfg.learner.sync_every = sync_every
    cfg.replay.capacity = 8192
    cfg.validate()
    devnull = open(os.devnull, "w")
    pipe = AsyncPipeline(cfg, logger=MetricLogger(stream=devnull),
                         log_every=10**9)
    t0 = time.perf_counter()
    try:
        result = pipe.run(learner_steps=steps, warmup_timeout=300.0)
    finally:
        wall = time.perf_counter() - t0
        devnull.close()
    import numpy as np

    assert np.isfinite(result["learner/loss"]), result
    p = result.get("pipeline", {})
    return {
        "depth": depth,
        "sync_every": sync_every,
        "steps": result["step"],
        "wall_s": round(wall, 2),
        "steps_per_sec": round(result["step"] / wall, 1),
        "host_syncs": p.get("host_syncs"),
        "syncs_per_1k_steps": p.get("syncs_per_1k_steps"),
        "overlap_gap_ms_p50": p.get("overlap_gap_ms_p50"),
        "overlap_gap_ms_p95": p.get("overlap_gap_ms_p95"),
        "gaps_observed": p.get("gaps_observed"),
        "inflight_at_exit": p.get("inflight"),
    }


def bench(steps: int, steps_per_call: int, sync_every: int) -> dict:
    """The pipeline_overlap sweep: strict vs overlapped depths on one
    workload.  ``strict`` runs depth 1 with sync_every=K, which routes it
    through the SAME overlapped runner (so host_syncs is counted on the
    same surface) while forcing every call — the legacy per-dispatch
    sync behavior."""
    points = [
        ("strict", 1, steps_per_call),
        ("depth2", 2, sync_every),
        ("depth4", 4, sync_every),
        # Second sync_every axis point: a 4x tighter drain cadence at the
        # same depth — separates the depth lever (flow control) from the
        # cadence lever (staleness bound) in the committed table.
        ("depth4_tight", 4, max(steps_per_call, sync_every // 4)),
    ]
    out: dict = {"points": {}}
    for name, depth, se in points:
        out["points"][name] = run_point(
            depth, se, steps, steps_per_call=steps_per_call
        )
    strict = out["points"]["strict"]
    d4 = out["points"]["depth4"]
    out["sync_reduction_x_depth4"] = round(
        strict["syncs_per_1k_steps"] / max(d4["syncs_per_1k_steps"], 1e-9), 1
    )
    out["steps_per_sec_delta_pct_depth4"] = round(
        (d4["steps_per_sec"] / max(strict["steps_per_sec"], 1e-9) - 1.0)
        * 100.0, 1
    )
    out["ingest_hidden"] = bool(
        d4["overlap_gap_ms_p50"] is not None
        and d4["overlap_gap_ms_p50"] <= 1.0
    )
    out["note"] = (
        "CPU host (mlp, random frames): sync counts and overlap "
        "accounting are platform-independent; steps/s here is not a "
        "device metric, and what a sync costs on the chip is not measured"
    )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="pipeline_smoke")
    ap.add_argument("--steps", type=int, default=4096)
    ap.add_argument("--steps-per-call", type=int, default=64)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--sync-every", type=int, default=1024)
    ap.add_argument("--slack", type=int, default=8,
                    help="allowed host_syncs beyond steps/sync_every "
                    "(flush-at-exit, warmup edges, poll-deadline blocks)")
    ap.add_argument("--bench", action="store_true",
                    help="run the depth sweep and print the "
                    "pipeline_overlap JSON instead of the CI assertions")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")

    if args.bench:
        print(json.dumps({"pipeline_overlap": bench(
            args.steps, args.steps_per_call, args.sync_every
        )}))
        return 0

    point = run_point(args.depth, args.sync_every, args.steps,
                      steps_per_call=args.steps_per_call)
    budget = args.steps / args.sync_every + args.slack
    checks = {
        "host_syncs_within_budget": bool(point["host_syncs"] <= budget),
        "clean_flush_at_exit": bool(point["inflight_at_exit"] == 0),
        "overlap_observed": bool(point["gaps_observed"] > 0),
    }
    verdict = {"pipeline_smoke": point, "budget": budget, "checks": checks,
               "ok": all(checks.values())}
    print(json.dumps(verdict))
    if not verdict["ok"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
