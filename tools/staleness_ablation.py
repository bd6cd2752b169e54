"""Sample-ahead staleness ablation — learning quality vs throughput mode.

The fused learner's ``sample_ahead`` mode draws all K batches of a dispatch
from call-entry priorities and restamps once after the scan
(replay/device.py:device_replay_sample_many): up to K steps of priority
staleness, traded for the per-step sampler's fixed op overhead.  Only
throughput had been measured — this script measures the
LEARNING-QUALITY side on real (small) tasks, strict vs sample-ahead at
K ∈ {256, 1024, 2048}.

Each variant trains the async fused pipeline on Catch and on the chain MDP
with identical budgets/seeds, then greedy-evaluates the learned policy
(evaluation.py).  Writes one JSONL record per variant.

Runs on any backend (CPU is fine — learning quality, not speed, is under
test; ``--cpu`` pins the CPU backend and leaves any chip free):

    python tools/staleness_ablation.py --cpu \
        --out demos/staleness_ablation.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_variant(env_name: str, sample_ahead: bool, K: int, steps: int,
                seed: int) -> dict:
    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.evaluation import make_evaluator
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    cfg = ApexConfig()
    cfg.env.name = env_name
    cfg.network = "mlp"  # the demos' learning configs (demos/README.md)
    cfg.seed = seed
    cfg.actor.num_actors = 16
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 32
    cfg.actor.epsilon = 0.7 if env_name.startswith("chain") else 0.4
    cfg.learner.device_replay = True
    cfg.learner.sample_ahead = sample_ahead
    cfg.learner.steps_per_call = K
    cfg.learner.min_replay_mem_size = 1000
    cfg.learner.replay_sample_size = 32
    cfg.learner.optimizer = "adam"
    cfg.learner.learning_rate = 1e-3
    # Equal across variants — and reachable at every K: the fused runtime
    # syncs targets at call boundaries rounded to a multiple of K, and
    # 2048 is a multiple of 256/1024/2048, so all variants sync at the
    # same steps and the ONLY difference is priority staleness.
    cfg.learner.q_target_sync_freq = 2048
    cfg.learner.max_grad_norm = None
    cfg.learner.total_steps = steps
    cfg.replay.capacity = 20_000
    cfg.validate()
    devnull = open(os.devnull, "w")
    pipe = AsyncPipeline(cfg, logger=MetricLogger(stream=devnull),
                         log_every=10**9)
    t0 = time.time()
    pipe.run(learner_steps=steps, warmup_timeout=300.0)
    wall = time.time() - t0
    devnull.close()
    ev = make_evaluator(
        pipe.comps.env_fns, pipe.comps.network,
        env_name=env_name, seed=seed,
    ).evaluate(pipe.fused.params_for_publish(), episodes=20)
    # Exploration-stream returns over the tail of training (the ε-ladder
    # fleet — noisier than eval but shows the training trajectory).
    tail = pipe.episode_returns[-100:]
    return {
        "env": env_name,
        "mode": f"sample_ahead K={K}" if sample_ahead else f"strict K={K}",
        "sample_ahead": sample_ahead,
        "K": K,
        "learner_steps": steps,
        "eval_score": round(ev.mean_score, 3),
        "eval_median": round(ev.median_score, 3),
        "train_tail_return": round(float(np.mean(tail)), 3) if tail else None,
        "wall_s": round(wall, 1),
        "seed": seed,
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="demos/staleness_ablation.jsonl")
    p.add_argument("--steps", type=int, default=8192)
    p.add_argument("--seeds", type=int, default=3)
    p.add_argument("--envs", default="catch,chain:6")
    p.add_argument("--cpu", action="store_true",
                   help="pin the CPU backend (leaves any TPU free)")
    args = p.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before the first jax import

    variants = [("strict", False, 256)] + [
        ("ahead", True, k) for k in (256, 1024, 2048)
    ]
    records = []
    with open(args.out, "w") as f:
        for env_name in args.envs.split(","):
            for _, ahead, K in variants:
                for seed in range(args.seeds):
                    rec = run_variant(env_name, ahead, K, args.steps, seed)
                    records.append(rec)
                    line = json.dumps(rec)
                    print(line)
                    f.write(line + "\n")
                    f.flush()
        # Per-variant mean eval score over seeds — the comparison table.
        for env_name in args.envs.split(","):
            for label, ahead, K in variants:
                scores = [r["eval_score"] for r in records
                          if r["env"] == env_name and r["K"] == K
                          and r["sample_ahead"] == ahead]
                summary = {
                    "summary": True, "env": env_name,
                    "mode": f"{label} K={K}",
                    "mean_eval_score": round(float(np.mean(scores)), 3),
                    "seeds": len(scores),
                }
                line = json.dumps(summary)
                print(line)
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
