"""The dedup frame ring's stored rows (replay/device_dedup.py RowFormat):
whatever the observation's shape, and whether a row is stored as one run of
words or as whole (8, 128) tiles, the ring built, ingested into, sampled
from and checkpointed holds byte for byte what a plain numpy ring holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ape_x_dqn_tpu.replay.device_dedup import (
    DedupDeviceReplayState,
    RowFormat,
    dedup_device_add_frames,
    dedup_device_add_transitions,
    dedup_sample_many,
    init_dedup_device_replay,
)
from ape_x_dqn_tpu.replay.device_dedup_dp import (
    build_sharded_dedup_add_frames,
    build_sharded_dedup_add_transitions,
    build_sharded_dedup_fused_learn_step,
    dedup_replay_specs,
)

# Paper rows (seven whole tiles a row: stored [Cf, 56, 128]), config3's rows,
# the suite's toy rows, a row of exactly 128 B, one that fills its 128 words
# exactly and one that fills one whole tile exactly (stored [Cf, 8, 128]).
OBS_SHAPES = [(84, 84, 4), (84, 84, 1), (6, 6, 1), (8, 16, 1), (16, 8, 4), (32, 32, 4)]
TILED = {(84, 84, 4): (56, 128), (32, 32, 4): (8, 128)}  # the others: [row_stride]
ids = lambda shapes: ["x".join(map(str, s)) for s in shapes]  # noqa: E731


def random_frames(seed, rows, obs_shape):
    return np.random.default_rng(seed).integers(
        0, 256, (rows, *obs_shape), dtype=np.uint8)


def full_state(frames, capacity, n_step=3):
    """The benchmark driver's pattern: a full ring built by keyword from a
    logical block, transition i referencing observation i and i + n_step."""
    cf = frames.shape[0]
    ref = jnp.arange(capacity, dtype=jnp.int32)
    return DedupDeviceReplayState(
        frames=frames, obs_ref=ref,
        next_ref=jnp.minimum(ref + n_step, cf - 1),
        action=jnp.zeros((capacity,), jnp.int32),
        reward=jnp.zeros((capacity,), jnp.float32),
        discount=jnp.ones((capacity,), jnp.float32),
        mass=jnp.ones((capacity,), jnp.float32),
        cursor=jnp.zeros((), jnp.int32),
        count=jnp.asarray(capacity, jnp.int32),
        fcount=jnp.asarray(cf, jnp.int32),
    )


def pack_counters(st):
    return st.replace(cursor=st.cursor[None], count=st.count[None],
                      fcount=st.fcount[None])


@pytest.mark.parametrize("obs_shape", OBS_SHAPES, ids=ids(OBS_SHAPES))
def test_state_built_in_jit_reads_back(obs_shape):
    x = random_frames(0, 40, obs_shape)
    state = jax.jit(lambda f: full_state(f, 32))(jnp.asarray(x))
    fmt = RowFormat.of(obs_shape, np.uint8)
    assert state.fmt == fmt
    assert fmt.row_shape == TILED.get(obs_shape, (fmt.row_stride,))
    assert state.rows.shape == (40, *fmt.row_shape)
    assert fmt.row_stride % 128 == 0 and state.rows.dtype == np.uint32
    assert 4 * fmt.row_stride - x[0].size < 512  # under one tile row of padding
    assert state.frame_capacity == 40 and state.capacity == 32
    assert state.seq_modulus == ((1 << 30) // 40) * 40
    np.testing.assert_array_equal(np.asarray(state.frames), x)
    # The logical view is not a leaf; the host copy unpacks the same bytes.
    assert len(jax.tree_util.tree_leaves(state)) == 10
    np.testing.assert_array_equal(jax.device_get(state).frames, x)
    np.testing.assert_array_equal(fmt.unpack(fmt.pack(x)), x)
    np.testing.assert_array_equal(np.asarray(state.rows), fmt.pack(x))
    # Either form holds the same words in the same order.
    words = np.ascontiguousarray(x.reshape(40, -1)).view(np.uint32)
    np.testing.assert_array_equal(
        np.asarray(state.rows).reshape(40, -1)[:, :words.shape[1]], words)


def test_long_block_packs_in_pieces(monkeypatch):
    """A block longer than the packing loop's piece, and not a multiple of
    it, packs to the same rows as the host's one-shot packing."""
    from ape_x_dqn_tpu.replay import device_dedup

    monkeypatch.setattr(device_dedup, "_PACK_BLOCK", 16)
    x = random_frames(1, 50, (6, 6, 1))
    fmt = RowFormat.of((6, 6, 1), np.uint8)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(fmt.pack)(jnp.asarray(x))), fmt.pack(x))


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.float32])
def test_row_format_follows_dtype(dtype):
    fmt = RowFormat.of((5, 3), dtype)
    x = (np.arange(7 * 15).reshape(7, 5, 3) % 251).astype(dtype)
    rows = fmt.pack(jnp.asarray(x))
    assert rows.shape == (7, 128) and rows.dtype == fmt.stored_dtype
    assert fmt.unpack(rows).dtype == dtype
    np.testing.assert_array_equal(np.asarray(fmt.unpack(rows)), x)
    np.testing.assert_array_equal(np.asarray(rows), fmt.pack(x))


class NumpyRing:
    """The same ring in plain numpy: rows land at seq % Cf."""

    def __init__(self, frames):
        self.frames, self.fcount = frames.copy(), frames.shape[0]

    def add(self, block):
        cf = self.frames.shape[0]
        self.frames[(self.fcount + np.arange(block.shape[0])) % cf] = block
        self.fcount += block.shape[0]


def ingest_call(i, rows, frames_per_call, fcount, obs_shape, n_step=3):
    """Call i's frame block and the transition block that references it."""
    block = random_frames(100 + i, frames_per_call, obs_shape)
    base = fcount + np.arange(rows)
    return block, base.astype(np.int32), (base + n_step).astype(np.int32)


@pytest.mark.parametrize("obs_shape", OBS_SHAPES, ids=ids(OBS_SHAPES))
def test_add_frames_and_sample_match_numpy_ring(obs_shape):
    cf, c, rows, per_call = 40, 32, 8, 12
    x = random_frames(2, cf, obs_shape)
    state = jax.jit(lambda f: full_state(f, c))(jnp.asarray(x))
    ring = NumpyRing(x)
    add_f = jax.jit(dedup_device_add_frames, donate_argnums=(0,))
    add_t = jax.jit(dedup_device_add_transitions, donate_argnums=(0,))
    for i in range(5):  # 60 frames through a ring of 40: the wrap is crossed
        block, oref, nref = ingest_call(i, rows, per_call, ring.fcount, obs_shape)
        ring.add(block)
        state = add_f(state, jnp.asarray(block))
        state = add_t(state, jnp.asarray(oref), jnp.asarray(nref),
                      jnp.zeros(rows, jnp.int32), jnp.zeros(rows, jnp.float32),
                      jnp.ones(rows, jnp.float32), jnp.ones(rows, jnp.float32))
        np.testing.assert_array_equal(np.asarray(state.frames), ring.frames)
    assert int(state.fcount) == ring.fcount
    batch = jax.jit(dedup_sample_many, static_argnums=(2, 3))(
        state, jax.random.PRNGKey(3), 3, 8)
    idx = np.asarray(batch.indices)
    assert batch.transition.obs.shape == (3, 8, *obs_shape)
    assert batch.transition.obs.dtype == np.uint8
    for got, ref in ((batch.transition.obs, state.obs_ref),
                     (batch.transition.next_obs, state.next_ref)):
        np.testing.assert_array_equal(
            np.asarray(got), ring.frames[np.asarray(ref)[idx] % cf])


@pytest.mark.parametrize("obs_shape", OBS_SHAPES[:3], ids=ids(OBS_SHAPES[:3]))
def test_sharded_ring_matches_numpy_per_shard(obs_shape):
    """The driver's sharded pattern on the CPU mesh: keyword construction
    from a logical block inside ``shard_map`` against
    ``dedup_replay_specs()`` (which knows no shape), a donated sharded add,
    the sampler per shard, ``frames`` read back from the global state."""
    from ape_x_dqn_tpu.parallel import make_mesh

    n, cf, c, rows, per_call = 4, 24, 16, 4, 8
    mesh = make_mesh(num_devices=n)
    specs = dedup_replay_specs()
    x = random_frames(4, n * cf, obs_shape)
    state = jax.jit(shard_map(
        lambda f: pack_counters(full_state(f, c)), mesh=mesh,
        in_specs=P("data"), out_specs=specs, check_vma=False))(jnp.asarray(x))
    assert state.rows.shape[0] == n * cf and state.cursor.shape == (n,)
    np.testing.assert_array_equal(np.asarray(state.frames), x)

    rings = [NumpyRing(x[d * cf:(d + 1) * cf]) for d in range(n)]
    add_f = build_sharded_dedup_add_frames(mesh)
    add_t = build_sharded_dedup_add_transitions(mesh)
    for i in range(5):  # 40 frames through each shard's 24: wrapped
        calls = [ingest_call(10 * i + d, rows, per_call, rings[d].fcount, obs_shape)
                 for d in range(n)]
        for ring, (block, _, _) in zip(rings, calls):
            ring.add(block)
        stack = lambda j, dt=None: jnp.asarray(  # noqa: E731
            np.stack([call[j] for call in calls]), dt)
        state = add_f(state, stack(0))
        state = add_t(
            state, stack(1), stack(2), jnp.zeros((n, rows), jnp.int32),
            jnp.zeros((n, rows), jnp.float32), jnp.ones((n, rows), jnp.float32),
            jnp.ones((n, rows), jnp.float32))
    want = np.concatenate([ring.frames for ring in rings])
    np.testing.assert_array_equal(np.asarray(state.frames), want)

    def sample(st, key):
        st = st.replace(cursor=st.cursor[0], count=st.count[0], fcount=st.fcount[0])
        key = jax.random.fold_in(key, jax.lax.axis_index("data"))
        b = dedup_sample_many(st, key, 2, 4, 0.4, "data")
        return b.transition.obs[None], b.transition.next_obs[None], b.indices[None]

    obs, next_obs, idx = jax.jit(shard_map(
        sample, mesh=mesh, in_specs=(specs, P()), out_specs=P("data"),
        check_vma=False))(state, jax.random.PRNGKey(5))
    oref = np.asarray(state.obs_ref).reshape(n, c)
    nref = np.asarray(state.next_ref).reshape(n, c)
    for d in range(n):
        i = np.asarray(idx)[d]
        np.testing.assert_array_equal(
            np.asarray(obs)[d], rings[d].frames[oref[d][i] % cf])
        np.testing.assert_array_equal(
            np.asarray(next_obs)[d], rings[d].frames[nref[d][i] % cf])


def test_sharded_fused_step_runs_on_a_ring_built_from_a_block():
    """The sharded fused builder takes the state the driver builds (specs
    without a format against a state with one) and leaves the rows alone."""
    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step, init_train_state, make_optimizer,
    )
    from ape_x_dqn_tpu.models.dueling import DuelingMLP
    from ape_x_dqn_tpu.parallel import make_mesh

    n, cf, c, obs_shape = 4, 24, 16, (6, 6, 1)
    mesh = make_mesh(num_devices=n)
    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("adam", learning_rate=1e-3)
    tstate = init_train_state(
        net, opt, jax.random.PRNGKey(0), np.zeros((1, *obs_shape), np.uint8))
    tstate = jax.device_put(
        jax.device_get(tstate), jax.sharding.NamedSharding(mesh, P()))
    step_fn = build_train_step(
        net, opt, sync_in_step=False, jit=False, grad_reduce_axis="data")
    fused = build_sharded_dedup_fused_learn_step(
        step_fn, mesh, 8, steps_per_call=3, target_sync_freq=3,
        sample_ahead=True)
    x = random_frames(6, n * cf, obs_shape)
    state = jax.jit(shard_map(
        lambda f: pack_counters(full_state(f, c)), mesh=mesh,
        in_specs=P("data"), out_specs=dedup_replay_specs(),
        check_vma=False))(jnp.asarray(x))
    tstate, state, metrics = fused(tstate, state, 0.4, jax.random.PRNGKey(7))
    assert np.isfinite(np.asarray(metrics.loss)).all()
    assert state.fmt == RowFormat.of(obs_shape, np.uint8)
    np.testing.assert_array_equal(np.asarray(state.frames), x)


def _learner(obs_shape, mesh=None, n=1):
    from ape_x_dqn_tpu.learner.train_step import init_train_state, make_optimizer
    from ape_x_dqn_tpu.models.dueling import DuelingMLP
    from ape_x_dqn_tpu.runtime.fused_dedup import FusedDedupLearner

    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("adam", learning_rate=1e-3)
    tstate = init_train_state(
        net, opt, jax.random.PRNGKey(0), jnp.zeros((1, *obs_shape), jnp.uint8))
    return FusedDedupLearner(
        net, opt, tstate, obs_shape, capacity=64 * n, batch_size=4 * n,
        steps_per_call=2, ingest_block=8 * n, target_sync_freq=4, mesh=mesh)


def _feed(fused, n, seqs, obs_shape):
    from test_checkpoint_inc import dchunk, prio

    for src in range(n):
        seq = seqs.get(src, 0)
        fused.add_chunk(
            prio(seed=src * 31 + seq),
            dchunk(src=src + 1, seq=seq, seed=src * 31 + seq,
                   carry=2 if seq else 0, obs=obs_shape))
        seqs[src] = seq + 1


CKPT_CASES = [((84, 84, 1), 1), ((6, 6, 1), 1), ((8, 16, 1), 1), ((6, 6, 1), 2),
              ((32, 32, 4), 1), ((32, 32, 4), 2)]   # the last two: tiled rows


@pytest.mark.parametrize(
    "obs_shape,n", CKPT_CASES,
    ids=[f"{'x'.join(map(str, s))}-dp{n}" for s, n in CKPT_CASES])
def test_incremental_checkpoint_round_trips_logical_rows(tmp_path, obs_shape, n):
    """What the runtime's checkpoint writes stays the logical
    ``[rows, *obs_shape]`` (a base's ``frames``, a delta's ``frame_rows``),
    so a checkpoint written before the ring stored packed rows restores; the
    restored ring holds the writer's rows bit for bit."""
    from ape_x_dqn_tpu.parallel import make_mesh
    from ape_x_dqn_tpu.utils.checkpoint_inc import (
        IncrementalCheckpointer, load_incremental_replay,
    )
    from test_checkpoint_inc import assert_same_state

    mesh = make_mesh(num_devices=n) if n > 1 else None
    fused = _learner(obs_shape, mesh, n)
    seqs = {}

    def advance():
        _feed(fused, n, seqs, obs_shape)
        fused.ingest_staged(drain=True)
        fused.train(0.5)

    for _ in range(3):
        advance()
    # Through the checkpointer: a base, a delta, a fresh learner.
    ck = IncrementalCheckpointer(str(tmp_path), fused, sync=True)
    ck.save(1)
    advance()
    ck.save(2)
    assert ck.stats()["deltas"] == 1
    fused2 = _learner(obs_shape, mesh, n)
    assert load_incremental_replay(str(tmp_path), fused2) == 2
    assert_same_state(fused.state_dict(), fused2.state_dict())
    np.testing.assert_array_equal(
        np.asarray(fused2._replay.rows), np.asarray(fused._replay.rows))

    # The same protocol by hand, to read what it writes.
    base = fused.delta_state_dict(force_base=True)
    cf = fused._replay.frame_capacity
    assert base["frames"].shape == (cf, *obs_shape)
    assert base["frames"].dtype == np.uint8
    np.testing.assert_array_equal(base["frames"], np.asarray(fused._replay.frames))
    advance()
    delta = fused.delta_state_dict()
    rows = np.asarray(delta["frame_rows"])
    assert rows.shape == (len(delta["frame_gidx"]), *obs_shape) and len(rows)
    np.testing.assert_array_equal(
        rows, np.asarray(fused._replay.frames)[delta["frame_gidx"]])
    fused3 = _learner(obs_shape, mesh, n)
    fused3.load_state_dict(base)
    fused3.apply_delta_state_dict(delta)
    assert_same_state(fused.state_dict(), fused3.state_dict())
    assert np.isfinite(np.asarray(fused3.train(0.5).loss)).all()


def test_footprint_is_rows_times_stride():
    """HBM sizing: ``frame_capacity x row_stride`` stored elements, within
    1.6% of the observations' own bytes at the package's real rows; a row
    stored as whole tiles is no byte larger."""
    for obs_shape in ((84, 84, 4), (84, 84, 1)):
        st = init_dedup_device_replay(64, obs_shape, frame_ratio=1.25)
        assert st.rows.shape == (80, *TILED.get(obs_shape, (st.fmt.row_stride,)))
        assert st.rows.nbytes == st.frame_capacity * st.fmt.row_stride * 4
        assert st.rows.nbytes == pytest.approx(
            80 * int(np.prod(obs_shape)), rel=0.016)
