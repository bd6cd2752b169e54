"""Child program for tests/test_multihost.py — one SPMD participant.

Run as: python tests/_multihost_child.py <process_id> <num_processes> <port>
Must be a standalone script (not under pytest): jax.distributed must
initialize before the backend exists, which a fresh process guarantees.
"""

import os
import sys


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = " ".join(
        [f for f in flags.split()
         if "force_host_platform_device_count" not in f]
        + ["--xla_force_host_platform_device_count=4"]
    )
    import jax

    pid, n, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "step"
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from ape_x_dqn_tpu.parallel.multihost import (
        host_value,
        initialize_multihost,
        local_shard,
    )

    initialize_multihost(f"127.0.0.1:{port}", num_processes=n, process_id=pid)
    if mode == "pipeline":
        return pipeline_mode(pid, n)

    import jax.numpy as jnp
    import numpy as np

    from ape_x_dqn_tpu.learner.train_step import init_train_state, make_optimizer
    from ape_x_dqn_tpu.models.dueling import DuelingMLP
    from ape_x_dqn_tpu.parallel import build_sharded_train_step, make_mesh, place_batch
    from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch

    assert len(jax.devices()) == 4 * n, jax.devices()
    net = DuelingMLP(num_actions=3, hidden_sizes=(32,))
    opt = make_optimizer("adam", learning_rate=1e-3)
    state = init_train_state(net, opt, jax.random.PRNGKey(0), jnp.zeros((1, 6)))
    mesh = make_mesh()  # the GLOBAL mesh: every process's devices
    B = 16
    r = np.random.default_rng(0)  # same stream in every process (SPMD)
    t = NStepTransition(
        obs=r.normal(size=(B, 6)).astype(np.float32),
        action=r.integers(0, 3, (B,)).astype(np.int32),
        reward=r.normal(size=(B,)).astype(np.float32),
        discount=np.full((B,), 0.97, np.float32),
        next_obs=r.normal(size=(B, 6)).astype(np.float32),
    )
    batch = PrioritizedBatch(
        transition=t,
        indices=np.arange(B, dtype=np.int32),
        is_weights=np.ones((B,), np.float32),
    )
    step_fn, sharded_state = build_sharded_train_step(
        net, opt, mesh, state, batch, target_sync_freq=100
    )
    gb = place_batch(batch, mesh)
    losses = []
    for _ in range(3):
        sharded_state, metrics = step_fn(sharded_state, gb)
        losses.append(float(host_value(metrics.loss)))
    mine = local_shard(metrics.priorities)
    # Each process owns B / n rows of the data-sharded priorities.
    assert mine.shape == (B // n,), mine.shape
    assert np.all(mine > 0)
    assert losses[2] < losses[0], losses
    print(f"RESULT {pid} {losses[2]:.8f} {int(host_value(sharded_state.step))}",
          flush=True)
    return 0


def pipeline_mode(pid: int, n: int) -> int:
    """The FULL async runtime per process — actors feeding a local replay,
    sampled local batches assembled into the global data-sharded batch,
    the all-reduced train step, per-host priority writeback — i.e. the
    multi-host Ape-X layout end to end on the CPU stand-in for a pod."""
    import jax
    import numpy as np

    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.parallel.multihost import host_value
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.num_actors = 4
    cfg.actor.T = 1_000_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 16
    cfg.learner.data_parallel = len(jax.devices())   # the GLOBAL mesh
    cfg.learner.replay_sample_size = 32
    cfg.learner.min_replay_mem_size = 128
    cfg.learner.optimizer = "adam"
    cfg.replay.capacity = 4096
    # cfg.seed IDENTICAL on every host: replicated param placement asserts
    # cross-process equality.  Per-host exploration comes from the
    # pipeline's process-indexed fleet seed base and sampler salt.
    pipe = AsyncPipeline(cfg, log_every=100)
    assert pipe._n_proc == n, pipe._n_proc
    result = pipe.run(learner_steps=60, warmup_timeout=180.0)
    loss = result["learner/loss"]
    step = int(host_value(pipe.comps.state.step))
    # Params identical across hosts: all-reduce kept them in lockstep.
    p0 = host_value(jax.tree_util.tree_leaves(pipe.comps.state.params)[0])
    digest = float(np.sum(np.abs(p0)))
    print(f"RESULT {pid} {loss:.8f} {step} {digest:.8f}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
