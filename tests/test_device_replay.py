"""Device replay and its sampler (the two-level inverse-CDF against the flat
cumsum oracle) on the CPU backend."""

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
from ape_x_dqn_tpu.ops.pallas.sampling import (
    _two_level_sample,
    _xla_sample,
    sample_indices,
)
from ape_x_dqn_tpu.replay.device import (
    build_fused_learn_step,
    device_replay_add,
    device_replay_restamp_last,
    device_replay_sample,
    device_replay_sample_many,
    device_replay_update_priorities,
    init_device_replay,
)
from ape_x_dqn_tpu.types import NStepTransition


def make_chunk(M, obs_shape=(8,), seed=0):
    r = np.random.default_rng(seed)
    return NStepTransition(
        obs=jnp.asarray(r.integers(0, 255, (M, *obs_shape), dtype=np.uint8)),
        action=jnp.asarray(r.integers(0, 3, (M,), dtype=np.int32)),
        reward=jnp.asarray(r.normal(size=(M,)).astype(np.float32)),
        discount=jnp.full((M,), 0.9, jnp.float32),
        next_obs=jnp.asarray(r.integers(0, 255, (M, *obs_shape), dtype=np.uint8)),
    )


class TestTwoLevelSampling:
    """The default sampler: radix-√C two-level inverse-CDF (the TPU-native
    sum-tree).  Integer masses make float32 prefix sums exact, so parity
    with the flat-cumsum oracle is bit-exact here."""

    def test_matches_xla_oracle(self, rng):
        pri = jnp.asarray(rng.integers(1, 100, 5000).astype(np.float32))
        total = float(pri.sum())
        targets = jnp.asarray(
            np.sort(rng.random(64)).astype(np.float32) * total * 0.999
        )
        a = _xla_sample(pri, targets)
        b = _two_level_sample(pri, targets, chunk=256)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_non_divisible_length_padded(self, rng):
        pri = jnp.asarray(rng.integers(1, 10, 777).astype(np.float32))
        total = float(pri.sum())
        targets = jnp.asarray((rng.random(32) * total * 0.999).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(_xla_sample(pri, targets)),
            np.asarray(_two_level_sample(pri, targets, chunk=64)),
        )

    def test_zero_mass_rows_skipped(self):
        pri = np.zeros(1024, np.float32)
        pri[700] = 1.0
        pri[1023] = 3.0
        targets = jnp.asarray([0.5, 1.5, 3.9], jnp.float32)
        out = _two_level_sample(jnp.asarray(pri), targets, chunk=128)
        assert list(np.asarray(out)) == [700, 1023, 1023]

    def test_default_dispatch_is_two_level(self, rng):
        pri = jnp.asarray(rng.integers(1, 50, 2048).astype(np.float32))
        total = float(pri.sum())
        targets = jnp.asarray((rng.random(16) * total * 0.999).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(sample_indices(pri, targets)),
            np.asarray(_two_level_sample(pri, targets)),
        )


class TestDeviceReplay:
    def test_add_rejects_chunk_wider_than_capacity(self):
        st = init_device_replay(8, (8,))
        with pytest.raises(ValueError, match="exceeds replay capacity"):
            device_replay_add(st, make_chunk(9), jnp.ones(9))

    def test_add_ring_semantics(self):
        st = init_device_replay(8, (8,))
        st = device_replay_add(st, make_chunk(6), jnp.ones(6))
        assert int(st.cursor) == 6 and int(st.count) == 6
        st = device_replay_add(st, make_chunk(4, seed=1), jnp.full(4, 2.0))
        assert int(st.cursor) == 2 and int(st.count) == 10
        # Slots 6,7,0,1 hold the new chunk's mass (2^0.6), slot 2 the old.
        mass = np.asarray(st.mass)
        assert mass[6] == pytest.approx(2 ** 0.6, rel=1e-5)
        assert mass[0] == pytest.approx(2 ** 0.6, rel=1e-5)
        assert mass[2] == pytest.approx(1.0, rel=1e-5)

    def test_sample_contents_roundtrip(self):
        st = init_device_replay(64, (8,))
        chunk = make_chunk(32, seed=3)
        st = device_replay_add(st, chunk, jnp.ones(32))
        batch = device_replay_sample(st, jax.random.PRNGKey(0), 16)
        idx = np.asarray(batch.indices)
        assert (idx < 32).all()
        np.testing.assert_array_equal(
            np.asarray(batch.transition.obs), np.asarray(chunk.obs)[idx]
        )
        np.testing.assert_array_equal(
            np.asarray(batch.transition.action), np.asarray(chunk.action)[idx]
        )

    def test_sampling_proportional(self):
        st = init_device_replay(4, (8,))
        st = device_replay_add(
            st, make_chunk(4), jnp.asarray([1.0, 1.0, 1.0, 100.0]),
            priority_exponent=1.0,
        )
        counts = np.zeros(4)
        for k in range(50):
            b = device_replay_sample(st, jax.random.PRNGKey(k), 64)
            counts += np.bincount(np.asarray(b.indices), minlength=4)
        frac = counts[3] / counts.sum()
        assert abs(frac - 100 / 103) < 0.02

    def test_update_priorities_scatter(self):
        st = init_device_replay(8, (8,))
        st = device_replay_add(st, make_chunk(8), jnp.ones(8), priority_exponent=1.0)
        st = device_replay_update_priorities(
            st, jnp.asarray([2, 5]), jnp.asarray([10.0, 20.0]), priority_exponent=1.0
        )
        mass = np.asarray(st.mass)
        assert mass[2] == 10.0 and mass[5] == 20.0 and mass[0] == 1.0

    def test_is_weights_beta_one(self):
        st = init_device_replay(4, (8,))
        st = device_replay_add(
            st, make_chunk(4), jnp.asarray([1.0, 1.0, 2.0, 4.0]),
            priority_exponent=1.0,
        )
        b = device_replay_sample(st, jax.random.PRNGKey(1), 128, beta=1.0)
        w = np.asarray(b.is_weights)
        idx = np.asarray(b.indices)
        if (idx <= 1).any() and (idx == 3).any():
            assert np.allclose(w[idx <= 1], 1.0)
            assert np.allclose(w[idx == 3], 0.25)


class TestFusedLearnStep:
    def test_chunk_in_k_steps_out(self):
        net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
        opt = make_optimizer("adam", learning_rate=1e-3)
        tstate = init_train_state(net, opt, jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.uint8))
        rstate = init_device_replay(256, (8,))
        rstate = device_replay_add(rstate, make_chunk(64), jnp.ones(64))
        base = build_train_step(net, opt, jit=False)
        fused = build_fused_learn_step(base, batch_size=16, steps_per_call=4)
        t2, r2, metrics = fused(
            tstate, rstate, make_chunk(32, seed=7), jnp.ones(32),
            0.4, jax.random.PRNGKey(1),
        )
        assert int(t2.step) == 4
        assert int(r2.count) == 96
        assert metrics.loss.shape == (4,)
        assert np.isfinite(np.asarray(metrics.loss)).all()
        # Priorities were restamped: mass no longer all equal.
        mass = np.asarray(r2.mass)[:96]
        assert mass.std() > 0

    def test_hoisted_target_sync_crossing(self):
        """With sync hoisted (sync_in_step=False + target_sync_freq=K·m),
        target params stay fixed until the scan crosses a freq multiple,
        then equal the online params at the call boundary."""
        net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
        opt = make_optimizer("adam", learning_rate=1e-2)
        tstate = init_train_state(net, opt, jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.uint8))
        rstate = init_device_replay(128, (8,))
        rstate = device_replay_add(rstate, make_chunk(64), jnp.ones(64))
        base = build_train_step(net, opt, sync_in_step=False, jit=False)
        fused = build_fused_learn_step(
            base, batch_size=16, steps_per_call=4, target_sync_freq=8,
        )
        t0_target = jax.tree_util.tree_leaves(tstate.target_params)[0].copy()
        # Call 1: step 0→4, no multiple of 8 crossed → target unchanged.
        tstate, rstate, _ = fused(tstate, rstate, make_chunk(8, seed=1),
                                  jnp.ones(8), 0.4, jax.random.PRNGKey(1))
        leaf = jax.tree_util.tree_leaves(tstate.target_params)[0]
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(t0_target))
        # Call 2: step 4→8 crosses 8 → target == online exactly.
        tstate, rstate, _ = fused(tstate, rstate, make_chunk(8, seed=2),
                                  jnp.ones(8), 0.4, jax.random.PRNGKey(2))
        for on, tg in zip(
            jax.tree_util.tree_leaves(tstate.params),
            jax.tree_util.tree_leaves(tstate.target_params),
        ):
            np.testing.assert_array_equal(np.asarray(on), np.asarray(tg))

    def test_include_ingest_false_signature(self):
        net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
        opt = make_optimizer("adam", learning_rate=1e-3)
        tstate = init_train_state(net, opt, jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.uint8))
        rstate = init_device_replay(128, (8,))
        rstate = device_replay_add(rstate, make_chunk(64), jnp.ones(64))
        base = build_train_step(net, opt, sync_in_step=False, jit=False)
        fused = build_fused_learn_step(
            base, batch_size=16, steps_per_call=3, include_ingest=False,
        )
        t2, r2, metrics = fused(tstate, rstate, 0.4, jax.random.PRNGKey(1))
        assert int(t2.step) == 3
        assert int(r2.count) == 64  # no ingest happened
        assert metrics.loss.shape == (3,)

    def test_bf16_knobs_still_learn(self):
        """The HBM-traffic knobs (bf16 second moment, bf16 target) must not
        break optimization: constant-target regression loss still falls."""
        net = DuelingMLP(num_actions=3, hidden_sizes=(32,))
        opt = make_optimizer(
            "rmsprop", learning_rate=3e-3, max_grad_norm=None,
            second_moment_dtype=jnp.bfloat16,
        )
        tstate = init_train_state(
            net, opt, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.uint8),
            target_dtype=jnp.bfloat16,
        )
        tgt_leaf = jax.tree_util.tree_leaves(tstate.target_params)[0]
        assert tgt_leaf.dtype == jnp.bfloat16
        rstate = init_device_replay(512, (8,))
        base = build_train_step(net, opt, sync_in_step=False, jit=False)
        fused = build_fused_learn_step(base, batch_size=32, steps_per_call=8,
                                       target_sync_freq=64)
        r = np.random.default_rng(0)
        losses = []
        for it in range(12):
            chunk = NStepTransition(
                obs=jnp.asarray(r.integers(0, 255, (32, 8), dtype=np.uint8)),
                action=jnp.asarray(r.integers(0, 3, (32,), dtype=np.int32)),
                reward=jnp.ones((32,), jnp.float32),
                discount=jnp.zeros((32,), jnp.float32),
                next_obs=jnp.asarray(r.integers(0, 255, (32, 8), dtype=np.uint8)),
            )
            tstate, rstate, metrics = fused(
                tstate, rstate, chunk, jnp.ones(32), 0.4, jax.random.PRNGKey(it)
            )
            losses.append(float(np.asarray(metrics.loss)[-1]))
        assert losses[-1] < losses[0] * 0.5, losses

    def test_fused_loop_learns(self):
        """Constant-target regression through the fused path: loss falls."""
        net = DuelingMLP(num_actions=3, hidden_sizes=(32,))
        opt = make_optimizer("adam", learning_rate=3e-3)
        tstate = init_train_state(net, opt, jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.uint8))
        rstate = init_device_replay(512, (8,))
        base = build_train_step(net, opt, jit=False)
        fused = build_fused_learn_step(base, batch_size=32, steps_per_call=8)
        r = np.random.default_rng(0)
        losses = []
        for it in range(12):
            chunk = NStepTransition(
                obs=jnp.asarray(r.integers(0, 255, (32, 8), dtype=np.uint8)),
                action=jnp.asarray(r.integers(0, 3, (32,), dtype=np.int32)),
                reward=jnp.ones((32,), jnp.float32),
                discount=jnp.zeros((32,), jnp.float32),
                next_obs=jnp.asarray(r.integers(0, 255, (32, 8), dtype=np.uint8)),
            )
            tstate, rstate, metrics = fused(
                tstate, rstate, chunk, jnp.ones(32), 0.4, jax.random.PRNGKey(it)
            )
            losses.append(float(np.asarray(metrics.loss)[-1]))
        assert losses[-1] < losses[0] * 0.5, losses


class TestSampleAhead:
    """The batched sample-ahead spellings (device_replay_sample_many /
    device_replay_restamp_last) behind ``sample_ahead=True``."""

    def test_sample_many_shapes_and_contents(self):
        st = init_device_replay(64, (8,))
        chunk = make_chunk(48, seed=3)
        st = device_replay_add(st, chunk, jnp.ones(48))
        b = device_replay_sample_many(st, jax.random.PRNGKey(0), 5, 16)
        assert b.indices.shape == (5, 16)
        assert b.transition.obs.shape == (5, 16, 8)
        assert b.is_weights.shape == (5, 16)
        idx = np.asarray(b.indices)
        assert (idx < 48).all()
        np.testing.assert_array_equal(
            np.asarray(b.transition.obs), np.asarray(chunk.obs)[idx]
        )
        # IS weights max-normalized per batch, not across the K axis.
        w = np.asarray(b.is_weights)
        np.testing.assert_allclose(w.max(axis=1), 1.0, rtol=1e-6)

    def test_sample_many_proportional(self):
        st = init_device_replay(4, (8,))
        st = device_replay_add(
            st, make_chunk(4), jnp.asarray([1.0, 1.0, 1.0, 100.0]),
            priority_exponent=1.0,
        )
        counts = np.zeros(4)
        for k in range(10):
            b = device_replay_sample_many(st, jax.random.PRNGKey(k), 8, 64)
            counts += np.bincount(np.asarray(b.indices).ravel(), minlength=4)
        frac = counts[3] / counts.sum()
        assert abs(frac - 100 / 103) < 0.02

    def test_restamp_last_wins_matches_sequential(self):
        """Batched restamp == K sequential scatters (last write wins)."""
        st = init_device_replay(16, (8,))
        st = device_replay_add(st, make_chunk(16), jnp.ones(16),
                               priority_exponent=1.0)
        r = np.random.default_rng(0)
        K, B = 6, 8
        indices = r.integers(0, 16, (K, B)).astype(np.int32)  # heavy dupes
        prios = r.random((K, B)).astype(np.float32) + 0.1
        seq = st
        for k in range(K):
            seq = device_replay_update_priorities(
                seq, jnp.asarray(indices[k]), jnp.asarray(prios[k]),
                priority_exponent=1.0,
            )
        batched = device_replay_restamp_last(
            st, jnp.asarray(indices), jnp.asarray(prios), priority_exponent=1.0
        )
        np.testing.assert_allclose(
            np.asarray(batched.mass), np.asarray(seq.mass), rtol=1e-6
        )

    def test_sample_ahead_fused_learns(self):
        """Constant-target regression through sample_ahead=True: loss falls
        and priorities were restamped."""
        net = DuelingMLP(num_actions=3, hidden_sizes=(32,))
        opt = make_optimizer("adam", learning_rate=3e-3)
        tstate = init_train_state(net, opt, jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.uint8))
        rstate = init_device_replay(512, (8,))
        base = build_train_step(net, opt, sync_in_step=False, jit=False)
        fused = build_fused_learn_step(
            base, batch_size=32, steps_per_call=8, target_sync_freq=64,
            sample_ahead=True,
        )
        r = np.random.default_rng(0)
        losses = []
        for it in range(12):
            chunk = NStepTransition(
                obs=jnp.asarray(r.integers(0, 255, (32, 8), dtype=np.uint8)),
                action=jnp.asarray(r.integers(0, 3, (32,), dtype=np.int32)),
                reward=jnp.ones((32,), jnp.float32),
                discount=jnp.zeros((32,), jnp.float32),
                next_obs=jnp.asarray(r.integers(0, 255, (32, 8), dtype=np.uint8)),
            )
            tstate, rstate, metrics = fused(
                tstate, rstate, chunk, jnp.ones(32), 0.4, jax.random.PRNGKey(it)
            )
            losses.append(float(np.asarray(metrics.loss)[-1]))
        assert int(tstate.step) == 96
        assert losses[-1] < losses[0] * 0.5, losses
        mass = np.asarray(rstate.mass)[:384]
        assert mass.std() > 0  # restamp happened
