"""Capture + summarize jax.profiler device traces.

Two captures:
  (a) ``--mode fused``   — one fused K-step call (ingest + K×[sample →
      train → restamp]) on the configured ring;
  (b) ``--mode pipeline`` — ~``--seconds`` of the contended async fused
      pipeline (actors + infeed + learner sharing the device).

Each capture writes a TensorBoard trace dir AND a self-contained JSON
summary parsed straight from the xplane protobuf (tensorflow +
tensorboard_plugin_profile are in this image): per-op totals on the
device plane, device busy vs. idle time, and the top ops — op-level truth
beside the subtractive-ablation *inference* of tools/profile_fused.py.  A
profiler that cannot start fails the capture (utils/profiling.trace raises).

    python tools/trace_capture.py --mode fused --out /tmp/trace_fused
    python tools/trace_capture.py --mode pipeline --seconds 10
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def summarize_xplane(logdir: str, top: int = 25) -> dict:
    """Parse the newest .xplane.pb under ``logdir`` into op-level totals."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = sorted(glob.glob(
        os.path.join(logdir, "**", "*.xplane.pb"), recursive=True
    ))
    if not paths:
        return {"error": f"no xplane.pb under {logdir}"}
    xspace = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        xspace.ParseFromString(f.read())
    out = {"xplane": paths[-1], "planes": []}
    for plane in xspace.planes:
        # Device planes carry the XLA op timeline; host planes the runtime.
        stats = {}
        span_lo, span_hi, busy = None, None, 0
        ev_meta = {m.id: m.name for m in plane.event_metadata.values()}
        for line in plane.lines:
            for ev in line.events:
                name = ev_meta.get(ev.metadata_id, str(ev.metadata_id))
                dur = ev.duration_ps / 1e6  # ps -> us
                rec = stats.setdefault(name, [0, 0.0])
                rec[0] += 1
                rec[1] += dur
                t0 = line.timestamp_ns * 1e3 + ev.offset_ps / 1e0  # ps units
                if span_lo is None or t0 < span_lo:
                    span_lo = t0
                if span_hi is None or t0 + ev.duration_ps > span_hi:
                    span_hi = t0 + ev.duration_ps
                busy += ev.duration_ps
        if not stats:
            continue
        ranked = sorted(stats.items(), key=lambda kv: -kv[1][1])[:top]
        span_us = (span_hi - span_lo) / 1e6 if span_lo is not None else 0.0
        out["planes"].append({
            "name": plane.name,
            "n_lines": len(plane.lines),
            "n_ops": len(stats),
            "span_us": round(span_us, 1),
            # busy sums line-overlapping events, so >100% of span is
            # possible on multi-line planes; per-line utilization is what
            # the top-op table below is read against.
            "busy_us": round(busy / 1e6, 1),
            "top_ops": [
                {"op": k, "count": v[0], "total_us": round(v[1], 1)}
                for k, v in ranked
            ],
        })
    return out


def capture_fused(logdir: str, steps_per_call: int, batch_size: int,
                  capacity: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step, init_train_state, make_optimizer,
    )
    from ape_x_dqn_tpu.models.dueling import build_network
    from ape_x_dqn_tpu.replay.device import (
        build_fused_learn_step, device_replay_add, init_device_replay,
    )
    from ape_x_dqn_tpu.utils.profiling import trace

    obs_shape, A, M = (84, 84, 1), 4, 256
    net = build_network("conv", A)
    opt = make_optimizer("rmsprop", max_grad_norm=None,
                         second_moment_dtype=jnp.bfloat16)
    step_fn = build_train_step(net, opt, sync_in_step=False, jit=False)
    K = steps_per_call
    fused = build_fused_learn_step(
        step_fn, batch_size, steps_per_call=K,
        target_sync_freq=K, sample_ahead=True,
    )
    rng = np.random.default_rng(0)
    from ape_x_dqn_tpu.types import NStepTransition

    chunk = jax.device_put(NStepTransition(
        obs=jnp.asarray(rng.integers(0, 255, (M, *obs_shape), dtype=np.uint8)),
        action=jnp.asarray(rng.integers(0, A, (M,), dtype=np.int32)),
        reward=jnp.asarray(rng.normal(size=(M,)).astype(np.float32)),
        discount=jnp.full((M,), 0.97, jnp.float32),
        next_obs=jnp.asarray(
            rng.integers(0, 255, (M, *obs_shape), dtype=np.uint8)),
    ))
    prio = jnp.ones((M,), jnp.float32)
    replay = init_device_replay(capacity, obs_shape)
    add = jax.jit(device_replay_add, donate_argnums=(0,))
    for _ in range(40):
        replay = add(replay, chunk, prio)
    state = init_train_state(
        net, opt, jax.random.PRNGKey(0),
        jnp.zeros((1, *obs_shape), jnp.uint8), target_dtype=jnp.bfloat16,
    )
    key = jax.random.PRNGKey(1)
    # Compile + warm OUTSIDE the trace.
    for _ in range(2):
        key, sub = jax.random.split(key)
        state, replay, metrics = fused(state, replay, chunk, prio, 0.4, sub)
    import numpy as _np

    _ = _np.asarray(metrics.loss)
    t0 = time.perf_counter()
    with trace(logdir):
        key, sub = jax.random.split(key)
        state, replay, metrics = fused(state, replay, chunk, prio, 0.4, sub)
        _ = _np.asarray(metrics.loss)  # force inside the trace window
    wall = time.perf_counter() - t0
    return {
        "mode": "fused",
        "steps_per_call": K, "batch_size": batch_size,
        "capacity": capacity, "wall_s_one_call": round(wall, 3),
        "us_per_step_incl_trace": round(wall / K * 1e6, 1),
    }


def capture_pipeline(logdir: str, seconds: float) -> dict:
    import numpy as np

    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.metrics import MetricLogger
    from ape_x_dqn_tpu.utils.profiling import trace

    cfg = ApexConfig()
    cfg.network = "conv"
    cfg.env.name = "random:84x84x1"
    cfg.actor.num_actors = 128
    cfg.actor.T = 10_000_000
    cfg.actor.flush_every = 16
    cfg.learner.device_replay = True
    cfg.learner.sample_ahead = True
    cfg.learner.steps_per_call = 512
    cfg.learner.publish_every = 4096
    cfg.learner.min_replay_mem_size = 5_000
    cfg.learner.optimizer = "rmsprop"
    cfg.learner.max_grad_norm = None
    cfg.learner.total_steps = 10**9
    cfg.replay.capacity = 100_000
    import threading

    devnull = open(os.devnull, "w")
    pipe = AsyncPipeline(cfg, logger=MetricLogger(stream=devnull),
                         log_every=10**9)
    err = []

    def run():
        try:
            pipe.run(learner_steps=10**9, warmup_timeout=300.0)
        except Exception as e:  # noqa: BLE001
            err.append(str(e))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # Wait until the contended steady state (past warmup) before tracing.
    deadline = time.time() + 300
    while pipe.learner_step < 2048 and time.time() < deadline:
        time.sleep(1.0)
    with trace(logdir):
        time.sleep(seconds)
    step_at_stop = pipe.learner_step
    pipe.stop_event.set()
    t.join(timeout=60)
    devnull.close()
    return {
        "mode": "pipeline",
        "seconds": seconds, "learner_step_at_capture": step_at_stop,
        "run_error": err[0] if err else None,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("fused", "pipeline"), default="fused")
    ap.add_argument("--out", default=None)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--steps-per-call", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=100_000)
    ap.add_argument("--summary-out", default=None,
                    help="write the JSON summary here too")
    args = ap.parse_args()
    from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    logdir = args.out or f"/tmp/trace_{args.mode}"
    if args.mode == "fused":
        rec = capture_fused(logdir, args.steps_per_call, args.batch_size,
                            args.capacity)
    else:
        rec = capture_pipeline(logdir, args.seconds)
    rec["summary"] = summarize_xplane(logdir)
    js = json.dumps(rec)
    print(js)
    if args.summary_out:
        with open(args.summary_out, "w") as f:
            f.write(js + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
