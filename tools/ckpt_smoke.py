"""Checkpoint round-trip smoke: save → SIGKILL → resume on the tiny config.

The verify_t1 gate (and tests/test_checkpoint_inc.py) for the incremental
async checkpoint subsystem end to end: a CHILD process trains the tiny
chain-MDP config with ``learner.checkpoint_incremental`` at a short cadence;
the parent waits until the committed chain holds at least
``kill_after_chunks`` chunk files — a base plus deltas, with further writes
plausibly in flight — then SIGKILLs the child mid-run and resumes IN
PROCESS from whatever the manifest committed: the learner step must land on
a committed checkpoint, the replay must come back non-empty, and training
must continue monotonically past the restored step.

``--dedup-dp`` runs the sharded-dedup shape instead (ROADMAP "wire the
dedup ring into checkpoint-resume at dp>1"): device_replay + replay.dedup +
data_parallel=2 over virtual CPU devices, killed and resumed mid-stream off
live actors — per-shard frame-ring cursors and dropped_carry ride the
chain.

Prints one JSON line; exit 0 iff every assertion held.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
if REPO not in sys.path:  # `python tools/ckpt_smoke.py` puts tools/ first
    sys.path.insert(0, REPO)

# The child runs on the CPU (run_smoke hands it JAX_PLATFORMS=cpu) and
# trains until killed: learner_steps is effectively unbounded.
_CHILD = """
import sys

from ape_x_dqn_tpu.config import ApexConfig
from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline

ckpt_dir, mode = sys.argv[1], sys.argv[2]
cfg = ApexConfig()
cfg.network = "mlp"
cfg.env.name = "chain:6"
cfg.actor.num_actors = 2
cfg.actor.T = 10_000_000
cfg.actor.flush_every = 8
cfg.actor.sync_every = 16
cfg.learner.optimizer = "adam"
cfg.learner.checkpoint_incremental = True
cfg.learner.checkpoint_base_every = 2
cfg.learner.checkpoint_dir = ckpt_dir
if mode == "dedup_dp":
    cfg.replay.dedup = True
    cfg.learner.device_replay = True
    cfg.learner.data_parallel = 2
    cfg.learner.steps_per_call = 4
    cfg.learner.ingest_block = 8
    cfg.learner.replay_sample_size = 16
    cfg.learner.min_replay_mem_size = 64
    cfg.learner.checkpoint_every = 8
    cfg.replay.capacity = 512
else:
    cfg.learner.min_replay_mem_size = 128
    cfg.learner.checkpoint_every = 20
    cfg.replay.capacity = 4096
cfg.validate()
print("child up", flush=True)
AsyncPipeline(cfg, log_every=100_000).run(
    learner_steps=100_000_000, warmup_timeout=240.0
)
"""


def _committed_chunks(inc_dir: str) -> int:
    manifest = os.path.join(inc_dir, "MANIFEST.json")
    if not os.path.exists(manifest):
        return 0
    try:
        with open(manifest) as f:
            return len(json.load(f)["chunks"])
    except (ValueError, KeyError, OSError):
        return 0  # racing the writer's os.replace — try again next poll


def run_smoke(ckpt_dir: str, mode: str = "host",
              kill_after_chunks: int = 2, timeout_s: float = 300.0) -> dict:
    """Spawn the training child, SIGKILL it once the chain is live, resume
    in process, and assert the round trip.  Returns the result record."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if mode == "dedup_dp":
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD, ckpt_dir,
         "dedup_dp" if mode == "dedup_dp" else "host"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
    )
    inc_dir = os.path.join(ckpt_dir, "replay_inc")
    deadline = time.monotonic() + timeout_s
    try:
        while _committed_chunks(inc_dir) < kill_after_chunks:
            if child.poll() is not None:
                raise RuntimeError(
                    "child exited before the chain committed:\n"
                    + child.stderr.read().decode(errors="replace")[-2000:]
                )
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"chain never reached {kill_after_chunks} committed "
                    f"chunks within {timeout_s}s"
                )
            time.sleep(0.05)
    finally:
        child.kill()  # SIGKILL — no atexit, no flush, torn tails welcome
        child.wait()
    chunks_at_kill = _committed_chunks(inc_dir)

    # ---- resume in process off whatever the manifest committed ----------
    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.checkpoint import latest_step

    committed_step = latest_step(ckpt_dir)
    assert committed_step is not None and committed_step > 0, (
        f"no committed state checkpoint under {ckpt_dir}"
    )
    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.num_actors = 2
    cfg.actor.T = 10_000_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 16
    cfg.learner.optimizer = "adam"
    cfg.learner.checkpoint_incremental = True
    cfg.learner.checkpoint_base_every = 2
    cfg.learner.checkpoint_dir = ckpt_dir
    cfg.learner.restore_from = True
    if mode == "dedup_dp":
        cfg.replay.dedup = True
        cfg.learner.device_replay = True
        cfg.learner.data_parallel = 2
        cfg.learner.steps_per_call = 4
        cfg.learner.ingest_block = 8
        cfg.learner.replay_sample_size = 16
        cfg.learner.min_replay_mem_size = 64
        cfg.learner.checkpoint_every = 8
        cfg.replay.capacity = 512
    else:
        cfg.learner.min_replay_mem_size = 128
        cfg.learner.checkpoint_every = 20
        cfg.replay.capacity = 4096
    cfg.validate()
    pipe = AsyncPipeline(cfg, log_every=100_000)
    resumed_step = pipe.learner_step
    assert resumed_step == committed_step, (
        f"resumed at {resumed_step}, newest committed state is "
        f"{committed_step}"
    )
    if mode == "dedup_dp":
        import numpy as np

        replay_size = pipe.fused.size
        # Per-shard cursors restored: the sharded ring's counters are
        # [n]-shaped — both shards must have made progress.
        counts = np.asarray(pipe.fused._replay.count)
        fcounts = np.asarray(pipe.fused._replay.fcount)
        assert counts.shape == (2,) and (counts > 0).all(), counts
        assert fcounts.shape == (2,) and (fcounts > 0).all(), fcounts
    else:
        replay_size = pipe.comps.replay.size()
    assert replay_size > 0, "replay came back empty"
    # Training continues monotonically past the restored step.
    target = resumed_step + (
        3 * cfg.learner.steps_per_call if mode == "dedup_dp" else 30
    )
    result = pipe.run(learner_steps=target, warmup_timeout=240.0)
    assert result["step"] >= target > resumed_step, result["step"]
    return {
        "mode": mode,
        "chunks_at_kill": chunks_at_kill,
        "committed_step": committed_step,
        "resumed_step": resumed_step,
        "replay_size_after_resume": int(replay_size),
        "continued_to_step": int(result["step"]),
        "ok": True,
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--dedup-dp", action="store_true",
                        help="sharded-dedup shape (device_replay + dedup + "
                        "data_parallel=2 on virtual CPU devices)")
    parser.add_argument("--kill-after-chunks", type=int, default=2)
    parser.add_argument("--timeout", type=float, default=300.0)
    args = parser.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"  # before the first jax import
    if args.dedup_dp:
        # The PARENT resumes the dp=2 mesh in process, so it needs the
        # virtual devices too — must land before jax's backend initializes
        # (jax is first imported inside run_smoke's resume).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    with tempfile.TemporaryDirectory(prefix="ckpt_smoke_") as d:
        out = run_smoke(
            os.path.join(d, "ckpt"),
            mode="dedup_dp" if args.dedup_dp else "host",
            kill_after_chunks=args.kill_after_chunks,
            timeout_s=args.timeout,
        )
    print(json.dumps({"ckpt_smoke": out}))


if __name__ == "__main__":
    main()
