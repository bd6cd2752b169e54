"""FusedDeviceLearner host driver + device-replay async-pipeline mode.

CPU backend (conftest's 8 virtual devices); the same code paths run on the
real chip via chip_smoke.py and the `learner.device_replay=true` CLI config.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.config import ApexConfig
from ape_x_dqn_tpu.learner.train_step import init_train_state, make_optimizer
from ape_x_dqn_tpu.models.dueling import DuelingMLP
from ape_x_dqn_tpu.runtime.fused_learner import FusedDeviceLearner
from ape_x_dqn_tpu.types import NStepTransition


def np_chunk(m, obs_shape=(8,), seed=0):
    r = np.random.default_rng(seed)
    return NStepTransition(
        obs=r.integers(0, 255, (m, *obs_shape), dtype=np.uint8),
        action=r.integers(0, 3, (m,), dtype=np.int32),
        reward=r.normal(size=(m,)).astype(np.float32),
        discount=np.full((m,), 0.9, np.float32),
        next_obs=r.integers(0, 255, (m, *obs_shape), dtype=np.uint8),
    )


def make_learner(**kw):
    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("rmsprop", learning_rate=1e-3, max_grad_norm=None)
    state = init_train_state(
        net, opt, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.uint8)
    )
    defaults = dict(
        obs_shape=(8,), capacity=256, batch_size=16, steps_per_call=4,
        ingest_block=32, target_sync_freq=100,
    )
    defaults.update(kw)
    return FusedDeviceLearner(net, opt, state, **defaults)


class TestFusedDeviceLearner:
    def test_staging_blocks_and_partial_tail(self):
        fl = make_learner(ingest_block=32)
        fl.add_chunk(np.ones(20, np.float32), np_chunk(20, seed=1))
        fl.add_chunk(np.ones(20, np.float32), np_chunk(20, seed=2))
        assert fl.staged_rows == 40
        ingested = fl.ingest_staged()
        # One full 32-block goes to HBM; the 8-row tail stays staged.
        assert ingested == 32
        assert fl.size == 32
        assert fl.staged_rows == 8

    def test_drain_flushes_tail(self):
        fl = make_learner(ingest_block=32)
        fl.add_chunk(np.ones(20, np.float32), np_chunk(20))
        assert fl.ingest_staged(drain=True) == 20
        assert fl.size == 20
        assert fl.staged_rows == 0

    def test_train_advances_k_steps(self):
        fl = make_learner(steps_per_call=4)
        fl.add_chunk(np.ones(64, np.float32), np_chunk(64))
        fl.ingest_staged()
        metrics = fl.train(beta=0.4)
        assert fl.step == 4
        assert metrics.loss.shape == (4,)
        assert np.isfinite(np.asarray(metrics.loss)).all()
        metrics = fl.train(beta=0.4)
        assert fl.step == 8

    def test_chunk_order_preserved_through_staging(self):
        """Rows must land in the ring in arrival order (FIFO eviction
        depends on it): obs row i of the ring == row i of the stream."""
        fl = make_learner(ingest_block=16)
        c1, c2 = np_chunk(10, seed=3), np_chunk(10, seed=4)
        fl.add_chunk(np.ones(10, np.float32), c1)
        fl.add_chunk(np.ones(10, np.float32), c2)
        fl.ingest_staged(drain=True)
        ring_obs = np.asarray(fl._replay.obs)[:20]
        want = np.concatenate([c1.obs, c2.obs])
        np.testing.assert_array_equal(ring_obs, want)


    def test_staged_tail_rides_the_snapshot(self):
        """Rows that ``ingest_staged()`` left behind (less than a block)
        are in ``state_dict`` and come back through ``load_state_dict``:
        a checkpoint loses nothing whatever the block alignment."""
        fl = make_learner(ingest_block=32)
        prio = np.arange(1, 41, dtype=np.float32)
        chunk = np_chunk(40, seed=5)
        fl.add_chunk(prio, chunk)
        assert fl.ingest_staged() == 32
        assert fl.staged_rows == 8
        snap = fl.state_dict()
        np.testing.assert_array_equal(snap["staged_prio"], prio[32:])
        np.testing.assert_array_equal(snap["staged_obs"], chunk.obs[32:])
        assert int(snap["count"]) == 32
        other = make_learner(ingest_block=32)
        other.load_state_dict(snap)
        assert other.size == 32 and other.staged_rows == 8
        assert other.ingest_staged(drain=True) == 8
        np.testing.assert_array_equal(
            np.asarray(other._replay.obs)[:40], chunk.obs)


def test_ingest_inside_the_fused_program_equals_a_separate_add():
    """``build_fused_learn_step(include_ingest=True)`` — the program the
    benchmark's ``ref_b32.learner`` cell times — against what the trainer
    runs, ``device_replay_add`` and then the program without ingest: the
    add is sequenced before the scan, so train state and ring agree bit
    for bit."""
    from ape_x_dqn_tpu.learner.train_step import build_train_step
    from ape_x_dqn_tpu.replay.device import (
        build_fused_learn_step,
        device_replay_add,
        init_device_replay,
    )

    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("rmsprop", learning_rate=1e-3, max_grad_norm=None)
    step_fn = build_train_step(net, opt, sync_in_step=False, jit=False)
    build = dict(batch_size=16, steps_per_call=4, target_sync_freq=8,
                 sample_ahead=True)
    folded = build_fused_learn_step(step_fn, include_ingest=True, **build)
    plain = build_fused_learn_step(step_fn, include_ingest=False, **build)
    add = jax.jit(device_replay_add)

    def run(fold: bool):
        state = init_train_state(
            net, opt, jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.uint8))
        ring = init_device_replay(128, (8,))
        rng = jax.random.PRNGKey(11)
        for call in range(3):
            chunk = jax.tree_util.tree_map(
                jnp.asarray, np_chunk(32, seed=20 + call))
            prio = jnp.asarray(np.random.default_rng(call).uniform(
                0.1, 2.0, 32).astype(np.float32))
            rng, sub = jax.random.split(rng)
            if fold:
                state, ring, m = folded(state, ring, chunk, prio, 0.4, sub)
            else:
                ring = add(ring, chunk, prio)
                state, ring, m = plain(state, ring, 0.4, sub)
        return jax.device_get((state, ring, m.loss))

    separate, inside = run(False), run(True)
    for a, b in zip(jax.tree_util.tree_leaves(separate),
                    jax.tree_util.tree_leaves(inside)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(separate[2]).all()


class TestAsyncPipelineFusedMode:
    def test_end_to_end_device_replay_mode(self, tmp_path):
        from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline

        cfg = ApexConfig()
        cfg.env.name = "chain:6"
        cfg.network = "mlp"
        cfg.actor.num_actors = 4
        cfg.actor.T = 50_000
        cfg.actor.flush_every = 8
        cfg.learner.device_replay = True
        cfg.learner.steps_per_call = 8
        cfg.learner.min_replay_mem_size = 128
        cfg.learner.replay_sample_size = 16
        cfg.learner.max_grad_norm = None
        cfg.learner.second_moment_dtype = "bfloat16"
        cfg.learner.target_dtype = "bfloat16"
        cfg.learner.checkpoint_every = 32
        cfg.learner.checkpoint_dir = str(tmp_path / "ckpt")
        cfg.replay.capacity = 2048
        pipe = AsyncPipeline(cfg, log_every=32)
        out = pipe.run(learner_steps=64, warmup_timeout=120)
        assert out["step"] >= 64
        assert out["replay_size"] >= 128
        assert pipe.store.version > 0
        assert np.isfinite(out["learner/loss"])
        # Checkpoint written from the fused state.
        assert (tmp_path / "ckpt").exists()
