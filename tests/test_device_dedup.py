"""Device frame-dedup ring: gather correctness, wrap-aware liveness, and
the fused-step oracle against the double-store layout (verdict item 1a,
device leg)."""

import functools

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
from test_dedup_rows import OBS_SHAPES

OBS = (4, 4, 1)


def frame(seq: int, obs_shape=OBS, dtype=np.uint8) -> np.ndarray:
    # every element its own value: a word taken apart in the wrong order shows
    return np.random.default_rng(seq).integers(0, 251, obs_shape).astype(dtype)


def make_stream(n_chunks=6, n_tx=8, seed=0, obs_shape=OBS, dtype=np.uint8):
    """Paired ingest streams: dedup (frames + abs refs) and the dense
    NStepTransition materialization, content-identical by construction.
    Chunk i contributes n_tx transitions over n_tx+1 fresh frames, with
    obs_i = frame(base+i), next_i = frame(base+i+1)."""
    made = functools.partial(frame, obs_shape=obs_shape, dtype=dtype)
    rng = np.random.default_rng(seed)
    dedup, dense, prios = [], [], []
    fbase = 0
    for _ in range(n_chunks):
        U = n_tx + 1
        frames = np.stack([made(fbase + i) for i in range(U)])
        obs_ref = fbase + np.arange(n_tx)
        next_ref = fbase + 1 + np.arange(n_tx)
        action = rng.integers(0, 3, n_tx).astype(np.int32)
        reward = rng.normal(size=n_tx).astype(np.float32)
        discount = np.full(n_tx, 0.97, np.float32)
        p = (np.abs(rng.normal(size=n_tx)) + 0.1).astype(np.float32)
        dedup.append((frames, obs_ref, next_ref, action, reward, discount))
        dense.append(NStepTransition(
            obs=np.stack([made(s) for s in obs_ref]),
            action=action, reward=reward, discount=discount,
            next_obs=np.stack([made(s) for s in next_ref]),
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


def build_learner(seed=0, obs_shape=OBS, dtype=np.uint8):
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
        np.zeros((1, *obs_shape), dtype),
    )
    step_fn = build_train_step(net, opt, sync_in_step=False, jit=False)
    return state, step_fn


def assert_same_bits(a, b, what):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=what), a, b)


def run_both_layouts(sample_ahead, K, B, calls, obs_shape=OBS, dtype=np.uint8):
    """Identical content ingested into both layouts, identical rng: after
    every call losses, priorities and parameters agree to the last bit, and
    the masses at the end.  Returns the common step count."""
    dedup, dense, prios = make_stream(
        n_chunks=6, n_tx=8, obs_shape=obs_shape, dtype=dtype)
    C = 64
    dd = init_dedup_device_replay(C, obs_shape, frame_capacity=128, obs_dtype=dtype)
    ds = init_device_replay(C, obs_shape, obs_dtype=dtype)
    dd = ingest_dedup(dd, dedup, prios)
    add = jax.jit(device_replay_add, donate_argnums=(0,))
    for t, p in zip(dense, prios):
        ds = add(ds, jax.device_put(t), jnp.asarray(p))

    state_a, step_a = build_learner(obs_shape=obs_shape, dtype=dtype)
    state_b, step_b = build_learner(obs_shape=obs_shape, dtype=dtype)
    fused_ds = build_fused_learn_step(
        step_a, B, steps_per_call=K, target_sync_freq=10,
        include_ingest=False, sample_ahead=sample_ahead,
    )
    fused_dd = build_dedup_fused_learn_step(
        step_b, B, steps_per_call=K, target_sync_freq=10,
        sample_ahead=sample_ahead,
    )
    rng = jax.random.PRNGKey(42)
    for i in range(calls):
        rng, sub = jax.random.split(rng)
        state_a, ds, m_a = fused_ds(state_a, ds, 0.4, sub)
        state_b, dd, m_b = fused_dd(state_b, dd, 0.4, sub)
        assert m_a.priorities.shape == (K, B)
        assert_same_bits(m_a.loss, m_b.loss, f"call {i} losses")
        assert_same_bits(m_a.priorities, m_b.priorities, f"call {i} priorities")
        assert_same_bits(state_a.params, state_b.params, f"call {i} parameters")
        assert_same_bits(state_a.target_params, state_b.target_params, f"call {i} target")
    assert_same_bits(ds.mass, dd.mass, "masses")
    assert int(state_a.step) == int(state_b.step)
    return int(state_a.step)


# tests/test_dedup_rows.py's rows, and a row that is no whole number of words
# (15 elements) in each stored width: four, two and one element a word.
ROW_CASES = [(s, np.uint8) for s in OBS_SHAPES] + [
    ((5, 3), np.uint8), ((5, 3), np.uint16), ((5, 3), np.float32)]


class TestFusedOracle:
    @pytest.mark.parametrize("sample_ahead", [False, True])
    def test_dedup_fused_equals_double_store_fused(self, sample_ahead):
        """The money test: identical content ingested into both layouts,
        identical rng → the K-step fused scan must produce identical
        params, metrics, and post-restamp masses."""
        assert run_both_layouts(sample_ahead, K=5, B=8, calls=3) == 15

    @pytest.mark.parametrize("sample_ahead", [False, True])
    @pytest.mark.parametrize(
        "obs_shape,dtype", ROW_CASES,
        ids=["x".join(map(str, s)) + "-" + np.dtype(d).name for s, d in ROW_CASES])
    def test_rows_fetched_in_the_step_are_the_rows_fetched_ahead(
            self, obs_shape, dtype, sample_ahead):
        """K > 1 with the rows fetched and taken apart inside the scan's
        body, whatever a row looks like: the double store, which gathers
        all K batches of observations ahead, is the oracle."""
        assert run_both_layouts(
            sample_ahead, K=3, B=8, calls=2, obs_shape=obs_shape, dtype=dtype) == 6
