"""Checkpoint save/resume of the full train state + replay (SURVEY §5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.learner.train_step import (
    build_train_step,
    init_train_state,
    make_optimizer,
)
from ape_x_dqn_tpu.models.dueling import DuelingMLP
from ape_x_dqn_tpu.replay import PrioritizedReplay
from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch
from ape_x_dqn_tpu.utils.checkpoint import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)


def make_state(seed=0):
    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("adam", learning_rate=1e-3)
    state = init_train_state(
        net, opt, jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.uint8)
    )
    return net, opt, state


def make_batch(B=16, seed=0):
    r = np.random.default_rng(seed)
    return PrioritizedBatch(
        transition=NStepTransition(
            obs=r.integers(0, 255, (B, 8), dtype=np.uint8),
            action=r.integers(0, 3, (B,), dtype=np.int32),
            reward=r.normal(size=(B,)).astype(np.float32),
            discount=np.full((B,), 0.9, np.float32),
            next_obs=r.integers(0, 255, (B, 8), dtype=np.uint8),
        ),
        indices=np.arange(B, dtype=np.int32),
        is_weights=np.ones((B,), np.float32),
    )


def test_roundtrip_full_state(tmp_path):
    net, opt, state = make_state()
    step_fn = build_train_step(net, opt)
    for i in range(3):
        state, _ = step_fn(state, jax.device_put(make_batch(seed=i)))
    save_checkpoint(str(tmp_path), state)
    assert latest_step(str(tmp_path)) == 3

    _, _, template = make_state(seed=99)  # different init
    restored, step = restore_checkpoint(str(tmp_path), template)
    assert step == 3
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(state)),
        jax.tree_util.tree_leaves(jax.device_get(restored)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resume_training_continues(tmp_path):
    """Optimizer state must survive: one more step after restore must equal
    the uninterrupted run bit-for-bit (same batches, same donation-free
    comparison)."""
    net, opt, state = make_state()
    step_fn = build_train_step(net, opt, jit=False)  # no donation: keep states
    s = state
    for i in range(2):
        s, _ = step_fn(s, jax.device_put(make_batch(seed=i)))
    save_checkpoint(str(tmp_path), s)
    s_cont, _ = step_fn(s, jax.device_put(make_batch(seed=7)))

    _, _, template = make_state(seed=5)
    restored, _ = restore_checkpoint(str(tmp_path), template)
    s_rest, _ = step_fn(restored, jax.device_put(make_batch(seed=7)))
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_cont.params)),
        jax.tree_util.tree_leaves(jax.device_get(s_rest.params)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_replay_snapshot_roundtrip(tmp_path):
    _, _, state = make_state()
    rep = PrioritizedReplay(64, (8,))
    b = make_batch(20)
    rep.add(np.abs(np.random.default_rng(0).normal(size=20)) + 0.1, b.transition)
    save_checkpoint(str(tmp_path), state, replay=rep)

    rep2 = PrioritizedReplay(64, (8,))
    _, _, template = make_state(seed=1)
    restore_checkpoint(str(tmp_path), template, replay=rep2)
    assert rep2.size() == 20
    assert np.isclose(rep2._tree.total, rep._tree.total)


def test_keep_prunes_old(tmp_path):
    net, opt, state = make_state()
    step_fn = build_train_step(net, opt)
    for i in range(5):
        state, _ = step_fn(state, jax.device_put(make_batch(seed=i)))
        save_checkpoint(str(tmp_path), state, keep=2)
    import os

    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert kept == ["step_4", "step_5"]


def test_missing_checkpoint_raises(tmp_path):
    _, _, template = make_state()
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path / "nope"), template)


def test_driver_restore_gate(tmp_path):
    """The config-gated resume path (reference learner.py:18-23 semantics)."""
    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.single_process import SingleProcessDriver

    def cfg():
        c = ApexConfig()
        c.env.name = "chain:6"
        c.network = "mlp"
        c.actor.num_actors = 2
        c.actor.flush_every = 4
        c.learner.min_replay_mem_size = 64
        c.replay.capacity = 1000
        c.learner.checkpoint_every = 10
        c.learner.checkpoint_dir = str(tmp_path)
        return c.validate()

    d1 = SingleProcessDriver(cfg())
    d1.run(learner_steps=10)
    assert latest_step(str(tmp_path)) == 10

    c2 = cfg()
    c2.learner.restore_from = str(tmp_path)
    d2 = SingleProcessDriver(c2)
    assert d2.learner_step == 10  # resumed, not fresh

    # Missing path falls back to scratch with a warning, like the reference.
    c3 = cfg()
    c3.learner.restore_from = str(tmp_path / "missing")
    d3 = SingleProcessDriver(c3)
    assert d3.learner_step == 0


def test_async_pipeline_kill_and_resume(tmp_path):
    """Train, checkpoint, then a NEW pipeline resumes —
    learner step AND replay contents both survive the restart."""
    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline

    def make_cfg():
        cfg = ApexConfig()
        cfg.network = "mlp"
        cfg.env.name = "chain:6"
        cfg.actor.num_actors = 2
        cfg.actor.T = 100_000
        cfg.actor.flush_every = 8
        cfg.actor.sync_every = 16
        cfg.learner.min_replay_mem_size = 128
        cfg.learner.optimizer = "adam"
        cfg.learner.checkpoint_every = 50
        cfg.learner.checkpoint_dir = str(tmp_path / "ckpt")
        cfg.replay.capacity = 4096
        return cfg

    pipe1 = AsyncPipeline(make_cfg(), log_every=100)
    pipe1.run(learner_steps=100, warmup_timeout=120.0)
    saved_size = pipe1.comps.replay.size()
    assert saved_size > 0

    cfg2 = make_cfg()
    cfg2.learner.restore_from = True  # "my checkpoint_dir"
    pipe2 = AsyncPipeline(cfg2, log_every=100)
    # Both the step counter and the buffer crossed the process boundary.
    assert pipe2.comps.learner_step == 100
    assert pipe2.learner_step == 100
    restored_size = pipe2.comps.replay.size()
    assert 0 < restored_size <= saved_size  # saved at the step-100 checkpoint
    # And training continues from there rather than restarting.
    result = pipe2.run(learner_steps=150, warmup_timeout=120.0)
    assert result["step"] >= 150


def test_fused_learner_replay_snapshot_roundtrip(tmp_path):
    """Device-replay (HBM ring) checkpoint leg: save via save_checkpoint
    (replay=fused learner), restore via load_replay_snapshot."""
    from ape_x_dqn_tpu.runtime.fused_learner import FusedDeviceLearner
    from ape_x_dqn_tpu.utils.checkpoint import load_replay_snapshot

    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("adam", learning_rate=1e-3)

    def make_fused():
        state = init_train_state(net, opt, jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.uint8))
        return FusedDeviceLearner(
            net, opt, state, (8,), capacity=128, batch_size=16,
            steps_per_call=4, ingest_block=32, target_sync_freq=8,
        )

    fused = make_fused()
    r = np.random.default_rng(0)
    M = 64
    fused.add_chunk(
        np.abs(r.normal(size=M)).astype(np.float32) + 0.1,
        NStepTransition(
            obs=r.integers(0, 255, (M, 8), dtype=np.uint8),
            action=r.integers(0, 3, (M,), dtype=np.int32),
            reward=r.normal(size=(M,)).astype(np.float32),
            discount=np.full((M,), 0.9, np.float32),
            next_obs=r.integers(0, 255, (M, 8), dtype=np.uint8),
        ),
    )
    fused.ingest_staged()
    fused.train(beta=0.4)
    path = save_checkpoint(str(tmp_path), fused.state, replay=fused)
    assert "replay.npz" in str(list(__import__("os").listdir(path)))

    fused2 = make_fused()
    assert load_replay_snapshot(str(tmp_path), fused2)
    assert fused2.size == fused.size
    np.testing.assert_array_equal(
        np.asarray(fused2._replay.mass), np.asarray(fused._replay.mass)
    )
    np.testing.assert_array_equal(
        np.asarray(fused2._replay.obs), np.asarray(fused._replay.obs)
    )
    # Restored ring trains immediately.
    metrics = fused2.train(beta=0.4)
    assert np.isfinite(np.asarray(metrics.loss)).all()


def test_periodic_fused_checkpoint_includes_staged_rows(tmp_path):
    """Round-3 verdict weak item 6: the periodic fused-mode save must drain
    staged-but-uningested host rows into the ring first, so a crash-restore
    from that checkpoint loses no experience."""
    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.runtime.fused_learner import FusedDeviceLearner
    from ape_x_dqn_tpu.utils.checkpoint import load_replay_snapshot

    cfg = ApexConfig()
    cfg.env.name = "chain:6"
    cfg.network = "mlp"
    cfg.learner.device_replay = True
    cfg.learner.steps_per_call = 4
    cfg.learner.replay_sample_size = 16
    cfg.learner.checkpoint_every = 4
    cfg.learner.checkpoint_dir = str(tmp_path)
    cfg.learner.min_replay_mem_size = 64
    cfg.replay.capacity = 256
    cfg.validate()
    pipe = AsyncPipeline(cfg)  # actors never started — driven by hand

    def chunk(M, seed):
        rr = np.random.default_rng(seed)
        return NStepTransition(
            obs=rr.integers(0, 255, (M, 6), dtype=np.uint8),
            action=rr.integers(0, 2, (M,), dtype=np.int32),
            reward=rr.normal(size=(M,)).astype(np.float32),
            discount=np.full((M,), 0.9, np.float32),
            next_obs=rr.integers(0, 255, (M, 6), dtype=np.uint8),
        )

    # 40 rows staged with ingest_block (256 default) > 40: a naive save
    # would snapshot an empty ring and lose them all.
    pipe.fused.add_chunk(np.ones(40, np.float32), chunk(40, 1))
    pipe.fused.ingest_staged()  # no full block → nothing lands
    assert pipe.fused.staged_rows == 40 and pipe.fused.size == 0
    path = pipe._save_fused_checkpoint()

    # Restore into a fresh ring: every staged row must be present.
    state2 = init_train_state(
        pipe.comps.network, pipe.comps.optimizer, jax.random.PRNGKey(9),
        jnp.zeros((1, 6), jnp.uint8),
    )
    fused2 = FusedDeviceLearner(
        pipe.comps.network, pipe.comps.optimizer, state2, (6,),
        capacity=256, batch_size=16, steps_per_call=4,
    )
    assert load_replay_snapshot(path, fused2)
    assert fused2.size == 40


def test_load_replay_snapshot_absent_returns_false(tmp_path):
    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("adam")
    state = init_train_state(net, opt, jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.uint8))
    save_checkpoint(str(tmp_path), state)  # no replay leg
    from ape_x_dqn_tpu.utils.checkpoint import load_replay_snapshot

    class Sink:
        def load_state_dict(self, d):
            raise AssertionError("must not be called")

    assert load_replay_snapshot(str(tmp_path), Sink()) is False


def test_per_host_replay_shards_roundtrip(tmp_path):
    """Multi-host checkpoint layout: process 0 saves state + its shard,
    other hosts save replay-only shards into the same step dir; each host
    restores ITS OWN shard (nothing lost, nothing duplicated)."""
    from ape_x_dqn_tpu.utils.checkpoint import (
        load_replay_snapshot,
        save_replay_snapshot,
    )

    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("adam")
    state = init_train_state(net, opt, jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.uint8))

    def filled_replay(fill_value):
        rep = PrioritizedReplay(64, (8,))
        n = 16
        rep.add(
            np.full(n, 1.0),
            NStepTransition(
                obs=np.full((n, 8), fill_value, np.uint8),
                action=np.zeros(n, np.int32),
                reward=np.ones(n, np.float32),
                discount=np.full(n, 0.9, np.float32),
                next_obs=np.full((n, 8), fill_value, np.uint8),
            ),
        )
        return rep

    r0, r1 = filled_replay(11), filled_replay(22)
    # Host 0 writes state + its shard; host 1 its shard only.
    save_checkpoint(str(tmp_path), state, replay=r0, replay_suffix="_h0")
    save_replay_snapshot(str(tmp_path), int(state.step), r1,
                         replay_suffix="_h1")
    # Each host restores its own shard.
    back0, back1 = PrioritizedReplay(64, (8,)), PrioritizedReplay(64, (8,))
    assert load_replay_snapshot(str(tmp_path), back0, replay_suffix="_h0")
    assert load_replay_snapshot(str(tmp_path), back1, replay_suffix="_h1")
    assert back0._obs.get(np.arange(1))[0, 0] == 11
    assert back1._obs.get(np.arange(1))[0, 0] == 22
    # The wrong suffix is absent, not silently cross-loaded.
    assert not load_replay_snapshot(str(tmp_path), PrioritizedReplay(64, (8,)),
                                    replay_suffix="_h9")
