"""Device frame-dedup ring: gather correctness, wrap-aware liveness, and
the fused-step oracle against the double-store layout (verdict item 1a,
device leg)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.replay.device import (
    build_fused_learn_step,
    device_replay_add,
    init_device_replay,
)
from ape_x_dqn_tpu.replay.device_dedup import (
    build_dedup_fused_learn_step,
    dedup_device_add_frames,
    dedup_device_add_transitions,
    dedup_sample_many,
    init_dedup_device_replay,
)
from ape_x_dqn_tpu.types import NStepTransition

OBS = (4, 4, 1)


def frame(seq: int) -> np.ndarray:
    return np.full(OBS, seq % 251, np.uint8)


def make_stream(n_chunks=6, n_tx=8, seed=0):
    """Paired ingest streams: dedup (frames + abs refs) and the dense
    NStepTransition materialization, content-identical by construction.
    Chunk i contributes n_tx transitions over n_tx+1 fresh frames, with
    obs_i = frame(base+i), next_i = frame(base+i+1)."""
    rng = np.random.default_rng(seed)
    dedup, dense, prios = [], [], []
    fbase = 0
    for _ in range(n_chunks):
        U = n_tx + 1
        frames = np.stack([frame(fbase + i) for i in range(U)])
        obs_ref = fbase + np.arange(n_tx)
        next_ref = fbase + 1 + np.arange(n_tx)
        action = rng.integers(0, 3, n_tx).astype(np.int32)
        reward = rng.normal(size=n_tx).astype(np.float32)
        discount = np.full(n_tx, 0.97, np.float32)
        p = (np.abs(rng.normal(size=n_tx)) + 0.1).astype(np.float32)
        dedup.append((frames, obs_ref, next_ref, action, reward, discount))
        dense.append(NStepTransition(
            obs=np.stack([frame(s) for s in obs_ref]),
            action=action, reward=reward, discount=discount,
            next_obs=np.stack([frame(s) for s in next_ref]),
        ))
        prios.append(p)
        fbase += U
    return dedup, dense, prios


def ingest_dedup(state, stream, prios, start=0, modulus=None):
    add_f = jax.jit(dedup_device_add_frames, donate_argnums=(0,))
    add_t = jax.jit(dedup_device_add_transitions, donate_argnums=(0,))
    Q = modulus or state.seq_modulus
    for (frames, oref, nref, a, r, d), p in zip(stream[start:], prios[start:]):
        state = add_f(state, jnp.asarray(frames))
        state = add_t(
            state,
            jnp.asarray(oref % Q, jnp.int32), jnp.asarray(nref % Q, jnp.int32),
            jnp.asarray(a), jnp.asarray(r), jnp.asarray(d), jnp.asarray(p),
        )
    return state


class TestDedupRing:
    def test_gather_matches_refs(self):
        dedup, dense, prios = make_stream()
        st = init_dedup_device_replay(64, OBS, frame_capacity=64)
        st = ingest_dedup(st, dedup, prios)
        batch = jax.tree_util.tree_map(
            lambda a: a[0],
            dedup_sample_many(st, jax.random.PRNGKey(0), 1, 16),
        )
        idx = np.asarray(batch.indices)
        oref = np.asarray(st.obs_ref)[idx]
        nref = np.asarray(st.next_ref)[idx]
        np.testing.assert_array_equal(
            np.asarray(batch.transition.obs), np.stack([frame(s) for s in oref])
        )
        np.testing.assert_array_equal(
            np.asarray(batch.transition.next_obs),
            np.stack([frame(s) for s in nref]),
        )

    def test_frame_death_sweep(self):
        """Frame ring smaller than the arrival stream: the oldest rows'
        masses go to zero in the same ingest that overwrites their frames."""
        dedup, _, prios = make_stream(n_chunks=8, n_tx=8)
        # 8 chunks x 9 frames = 72 frames > Cf=32: early chunks age out.
        st = init_dedup_device_replay(64, OBS, frame_capacity=32)
        st = ingest_dedup(st, dedup, prios)
        mass = np.asarray(st.mass)
        age = (int(st.fcount) - np.asarray(st.obs_ref)) % st.seq_modulus
        rows = np.arange(48)  # 48 rows written, ring not yet wrapped
        dead = age[rows] > 32
        assert dead.any() and (~dead).any()
        assert (mass[rows][dead] == 0).all()
        assert (mass[rows][~dead] > 0).all()

    def test_seq_wrap_is_transparent(self):
        """Start the frame counter just below the modulus Q: ingest crosses
        the int32-safe wrap and sampling still gathers the right frames."""
        dedup, _, prios = make_stream(n_chunks=4, n_tx=8)
        st = init_dedup_device_replay(64, OBS, frame_capacity=32)
        Q = st.seq_modulus
        start = Q - 17  # wraps mid-stream
        st = st.replace(fcount=jnp.int32(start))
        shifted = [
            (f, (o + start) % Q, (n + start) % Q, a, r, d)
            for f, o, n, a, r, d in dedup
        ]
        st = ingest_dedup(st, shifted, prios, modulus=Q)
        assert int(st.fcount) == (start + 4 * 9) % Q
        batch = jax.tree_util.tree_map(
            lambda a: a[0],
            dedup_sample_many(st, jax.random.PRNGKey(1), 1, 16),
        )
        idx = np.asarray(batch.indices)
        # Recover the pre-shift seq to predict content.
        oref = (np.asarray(st.obs_ref)[idx] - start) % Q
        np.testing.assert_array_equal(
            np.asarray(batch.transition.obs), np.stack([frame(s) for s in oref])
        )

    def test_footprint_observable(self):
        # At a real row: the stored rows are the HBM footprint, each padded
        # to whole 128-word tiles (84x84x1: +1.6%; a toy row pads to 512 B).
        obs = (84, 84, 1)
        dd = init_dedup_device_replay(64, obs, frame_ratio=1.25)
        ds = init_device_replay(64, obs)
        frames_dd = dd.rows.nbytes
        frames_ds = ds.obs.nbytes + ds.next_obs.nbytes
        assert frames_dd == pytest.approx(0.625 * frames_ds, rel=0.02)
        assert dd.frames.nbytes == 0.625 * frames_ds


def build_learner(seed=0):
    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step,
        init_train_state,
        make_optimizer,
    )
    from ape_x_dqn_tpu.models.dueling import DuelingMLP

    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("adam", learning_rate=1e-3)
    state = init_train_state(
        net, opt, jax.random.PRNGKey(seed),
        np.zeros((1, *OBS), np.uint8),
    )
    step_fn = build_train_step(net, opt, sync_in_step=False, jit=False)
    return state, step_fn


class TestFusedOracle:
    @pytest.mark.parametrize("sample_ahead", [False, True])
    def test_dedup_fused_equals_double_store_fused(self, sample_ahead):
        """The money test: identical content ingested into both layouts,
        identical rng → the K-step fused scan must produce identical
        params, metrics, and post-restamp masses."""
        dedup, dense, prios = make_stream(n_chunks=6, n_tx=8)
        C = 64
        dd = init_dedup_device_replay(C, OBS, frame_capacity=128)
        ds = init_device_replay(C, OBS)
        dd = ingest_dedup(dd, dedup, prios)
        add = jax.jit(device_replay_add, donate_argnums=(0,))
        for t, p in zip(dense, prios):
            ds = add(ds, jax.device_put(t), jnp.asarray(p))

        state_a, step_a = build_learner()
        state_b, step_b = build_learner()
        K, B = 5, 8
        fused_ds = build_fused_learn_step(
            step_a, B, steps_per_call=K, target_sync_freq=10,
            include_ingest=False, sample_ahead=sample_ahead,
        )
        fused_dd = build_dedup_fused_learn_step(
            step_b, B, steps_per_call=K, target_sync_freq=10,
            sample_ahead=sample_ahead,
        )
        rng = jax.random.PRNGKey(42)
        for i in range(3):
            rng, sub = jax.random.split(rng)
            state_a, ds, m_a = fused_ds(state_a, ds, 0.4, sub)
            state_b, dd, m_b = fused_dd(state_b, dd, 0.4, sub)
            np.testing.assert_array_equal(
                np.asarray(m_a.priorities), np.asarray(m_b.priorities),
                err_msg=f"call {i} priorities",
            )
            jax.tree_util.tree_map(
                lambda x, y: np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), rtol=0, atol=0
                ),
                state_a.params, state_b.params,
            )
        np.testing.assert_array_equal(
            np.asarray(ds.mass), np.asarray(dd.mass)
        )
        assert int(state_a.step) == int(state_b.step) == 15
