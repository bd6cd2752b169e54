"""Fused device replay × data parallelism (replay/device_dp.py).

Round-3 verdict top item: the two fast paths must combine.  These tests run
on the conftest's 8 virtual CPU devices and pin the sharded semantics
against single-device oracles:

  * ingest splits chunks contiguously over shards' rings;
  * the per-shard sampler's indices and IS weights match a numpy
    inverse-CDF oracle of the realized sampling law q = (m_i/M_s)/n;
  * the strict-PER fused scan (sample → train with grad all-reduce →
    restamp, K steps) matches a hand-run emulation built from the
    single-device sample/update functions + a concatenated-batch train
    step — params AND per-shard restamped masses;
  * the async pipeline runs end-to-end in fused+DP mode;
  * checkpoints round-trip the sharded ring (with staged rows).
"""

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
from ape_x_dqn_tpu.parallel import make_mesh
from ape_x_dqn_tpu.replay.device import (
    DeviceReplayState,
    device_replay_sample,
    device_replay_sample_many,
    device_replay_update_priorities,
)
from ape_x_dqn_tpu.replay.device_dp import (
    _local,
    build_sharded_fused_learn_step,
    build_sharded_replay_add,
    init_sharded_device_replay,
    replay_specs,
)
from ape_x_dqn_tpu.runtime.fused_learner import FusedDeviceLearner
from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch


def np_chunk(M, obs_shape=(8,), seed=0):
    r = np.random.default_rng(seed)
    return NStepTransition(
        obs=r.integers(0, 255, (M, *obs_shape), dtype=np.uint8),
        action=r.integers(0, 3, (M,), dtype=np.int32),
        reward=r.normal(size=(M,)).astype(np.float32),
        discount=np.full((M,), 0.9, np.float32),
        next_obs=r.integers(0, 255, (M, *obs_shape), dtype=np.uint8),
    )


class TestShardedIngest:
    def test_chunk_splits_contiguously_over_shards(self):
        n, C = 4, 64  # C_local = 16
        mesh = make_mesh(num_devices=n)
        state = init_sharded_device_replay(C, (8,), mesh)
        add = build_sharded_replay_add(mesh)
        chunk = np_chunk(32, seed=1)
        state = add(state, jax.device_put(chunk), jnp.ones(32))
        got = jax.device_get(state)
        # Shard d's ring occupies global rows [d*16, (d+1)*16); its first 8
        # slots hold chunk rows [d*8, (d+1)*8).
        for d in range(n):
            np.testing.assert_array_equal(
                got.obs[d * 16: d * 16 + 8], chunk.obs[d * 8: (d + 1) * 8]
            )
        np.testing.assert_array_equal(np.asarray(got.cursor), [8] * n)
        np.testing.assert_array_equal(np.asarray(got.count), [8] * n)

    def test_capacity_must_divide(self):
        mesh = make_mesh(num_devices=4)
        with pytest.raises(ValueError, match="divide"):
            init_sharded_device_replay(30, (8,), mesh)


def _manual_global_state(mesh, n, C_local, mass_global):
    """A FULL sharded ring with given integer masses and arbitrary rows."""
    C = n * C_local
    chunk = np_chunk(C, seed=7)
    state = init_sharded_device_replay(C, (8,), mesh)
    add = build_sharded_replay_add(mesh)
    # Priorities whose ^0.6 mass we overwrite below; rows land contiguous.
    state = add(state, jax.device_put(chunk), jnp.ones(C))
    state = state.replace(
        mass=jax.device_put(
            jnp.asarray(mass_global, jnp.float32), state.mass.sharding
        )
    )
    return state, chunk


class TestShardedSampler:
    def test_indices_and_weights_match_numpy_oracle(self):
        """The realized per-shard law is q_i = (m_i / M_s) / n; indices come
        from a stratified inverse-CDF over the shard's mass and weights are
        (N_global · q_i)^-β normalized by the GLOBAL batch max."""
        n, C_local, K, B = 4, 16, 3, 8
        beta = 0.7
        mesh = make_mesh(num_devices=n)
        r = np.random.default_rng(3)
        # Integer masses -> exact float32 prefix sums -> bit-exact oracle.
        mass = r.integers(1, 50, n * C_local).astype(np.float32)
        state, _ = _manual_global_state(mesh, n, C_local, mass)
        rng = jax.random.PRNGKey(11)

        def run(st, key):
            def body(st_l):
                loc = _local(st_l)
                k = jax.random.fold_in(key, jax.lax.axis_index("data"))
                b = device_replay_sample_many(
                    loc, k, K, B, beta, axis_name="data"
                )
                return b.indices, b.is_weights

            from jax.sharding import PartitionSpec as P

            return jax.shard_map(
                body, mesh=mesh, in_specs=(replay_specs(),),
                out_specs=(P(None, "data"), P(None, "data")),
            )(st)

        idx_g, w_g = jax.device_get(run(state, rng))  # [K, n*B] each

        # ---- numpy oracle ----
        N_global = n * C_local  # every slot filled
        want_idx = np.zeros((K, n * B), np.int64)
        raw_w = np.zeros((K, n * B), np.float64)
        for s in range(n):
            m_s = mass[s * C_local:(s + 1) * C_local]
            total = np.float32(m_s.sum())
            u = np.asarray(
                jax.random.uniform(jax.random.fold_in(rng, s), (K, B))
            )
            targets = (
                (np.arange(B, dtype=np.float32)[None, :] + u)
                * (total / np.float32(B))
            ).astype(np.float32)
            targets = np.minimum(targets, total * np.float32(1.0 - 1e-7))
            cdf = np.cumsum(m_s, dtype=np.float32)
            idx = np.searchsorted(cdf, targets, side="right")
            idx = np.clip(idx, 0, C_local - 1)
            q = m_s[idx] / total / n
            want_idx[:, s * B:(s + 1) * B] = idx
            raw_w[:, s * B:(s + 1) * B] = (N_global * q) ** (-beta)
        want_w = raw_w / raw_w.max(axis=1, keepdims=True)

        np.testing.assert_array_equal(idx_g, want_idx)
        np.testing.assert_allclose(w_g, want_w, rtol=1e-5)


class TestShardedFusedStrict:
    def test_matches_concat_batch_emulation(self):
        """The whole strict-PER fused call — K × [per-shard sample → train
        with pmean'd grads → per-shard restamp] — against an emulation
        from single-device pieces: per-shard sampling with hand-computed
        global IS weights, ONE train step on the concatenated global batch,
        per-shard priority updates.  Params and restamped masses agree."""
        n, C_local, K, B_local = 2, 32, 3, 4
        B = n * B_local
        pexp, beta = 0.6, 0.5
        mesh = make_mesh(num_devices=n)
        r = np.random.default_rng(5)
        mass = r.integers(1, 30, n * C_local).astype(np.float32)
        state_g, chunk = _manual_global_state(mesh, n, C_local, mass)

        net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
        # Plain SGD: linear in the gradient, so emulation mismatches surface
        # as-is instead of being amplified to ±lr by RMSProp's rsqrt(nu≈0)
        # (first steps of rmsprop are ~sign(g) — float noise flips signs).
        # Debugged at K=1: loss/priorities agree to 1e-7 under rmsprop too.
        import optax

        opt = optax.sgd(1e-3)
        t0 = init_train_state(
            net, opt, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.uint8)
        )
        rng = jax.random.PRNGKey(42)

        # --- sharded run ---
        from jax.sharding import NamedSharding, PartitionSpec as P

        step_sh = build_train_step(
            net, opt, loss_kind="huber", sync_in_step=False,
            grad_reduce_axis="data", jit=False,
        )
        fused = build_sharded_fused_learn_step(
            step_sh, mesh, B, steps_per_call=K,
            priority_exponent=pexp, target_sync_freq=None,
        )
        t_repl = jax.jit(lambda s: s, out_shardings=NamedSharding(mesh, P()))(t0)
        t_f, r_f, metrics = fused(t_repl, state_g, beta, rng)
        got_params = jax.device_get(t_f.params)
        got_mass = np.asarray(jax.device_get(r_f.mass))

        # --- emulation ---
        step_em = build_train_step(
            net, opt, loss_kind="huber", sync_in_step=False, jit=False,
        )
        locals_ = []
        for s in range(n):
            sl = slice(s * C_local, (s + 1) * C_local)
            locals_.append(DeviceReplayState(
                obs=jnp.asarray(chunk.obs[sl]),
                next_obs=jnp.asarray(chunk.next_obs[sl]),
                action=jnp.asarray(chunk.action[sl], jnp.int32),
                reward=jnp.asarray(chunk.reward[sl]),
                discount=jnp.asarray(chunk.discount[sl]),
                mass=jnp.asarray(mass[sl]),
                cursor=jnp.zeros((), jnp.int32),
                count=jnp.asarray(C_local, jnp.int32),
            ))
        rngs = [jax.random.split(jax.random.fold_in(rng, s), K)
                for s in range(n)]
        t_em = t0
        N_global = float(n * C_local)
        for k in range(K):
            parts, idxs = [], []
            for s in range(n):
                b = device_replay_sample(locals_[s], rngs[s][k], B_local, beta)
                parts.append(jax.device_get(b))
                idxs.append(np.asarray(b.indices))
            # Correct the IS weights to the sharded law (the single-ring
            # sampler normalized per-shard with local N).
            raw = []
            for s in range(n):
                m_s = np.asarray(locals_[s].mass)
                q = m_s[idxs[s]] / m_s.sum() / n
                raw.append((N_global * q) ** (-beta))
            wmax = max(float(w.max()) for w in raw)
            weights = np.concatenate([w / wmax for w in raw]).astype(np.float32)
            batch = PrioritizedBatch(
                transition=NStepTransition(
                    obs=np.concatenate([p.transition.obs for p in parts]),
                    action=np.concatenate([p.transition.action for p in parts]),
                    reward=np.concatenate([p.transition.reward for p in parts]),
                    discount=np.concatenate(
                        [p.transition.discount for p in parts]
                    ),
                    next_obs=np.concatenate(
                        [p.transition.next_obs for p in parts]
                    ),
                ),
                indices=np.concatenate(idxs).astype(np.int32),
                is_weights=weights,
            )
            t_em, m_em = step_em(t_em, jax.device_put(batch))
            prios = np.asarray(m_em.priorities)
            for s in range(n):
                locals_[s] = device_replay_update_priorities(
                    locals_[s], jnp.asarray(idxs[s]),
                    jnp.asarray(prios[s * B_local:(s + 1) * B_local]), pexp,
                )

        want_params = jax.device_get(t_em.params)
        for a, b in zip(jax.tree_util.tree_leaves(got_params),
                        jax.tree_util.tree_leaves(want_params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            )
        want_mass = np.concatenate(
            [np.asarray(l.mass) for l in locals_]
        )
        np.testing.assert_allclose(got_mass, want_mass, rtol=1e-5, atol=1e-7)
        # The scan really ran K steps and losses were finite.
        assert int(jax.device_get(t_f.step)) == K
        assert np.isfinite(np.asarray(metrics.loss)).all()


class TestFusedDPRuntime:
    def test_pipeline_end_to_end(self):
        from ape_x_dqn_tpu.config import ApexConfig
        from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline

        cfg = ApexConfig()
        cfg.env.name = "chain:6"
        cfg.network = "mlp"
        cfg.actor.num_actors = 4
        cfg.actor.flush_every = 8
        cfg.learner.device_replay = True
        cfg.learner.data_parallel = 4
        cfg.learner.steps_per_call = 8
        cfg.learner.min_replay_mem_size = 128
        cfg.learner.replay_sample_size = 16
        cfg.learner.max_grad_norm = None
        cfg.replay.capacity = 2048
        pipe = AsyncPipeline(cfg, log_every=32)
        out = pipe.run(learner_steps=64, warmup_timeout=120)
        assert out["step"] >= 64
        assert np.isfinite(out["learner/loss"])
        assert out["replay_size"] >= 128

    def test_capacity_divisibility_validated(self):
        from ape_x_dqn_tpu.config import ApexConfig

        cfg = ApexConfig()
        cfg.learner.device_replay = True
        cfg.learner.data_parallel = 4
        cfg.replay.capacity = 100_002
        with pytest.raises(ValueError, match="capacity must be divisible"):
            cfg.validate()


class TestShardedSnapshot:
    def test_roundtrip_with_staged_rows(self):
        net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
        opt = make_optimizer("adam", learning_rate=1e-3)
        mesh = make_mesh(num_devices=4)

        def make(seed):
            st = init_train_state(
                net, opt, jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.uint8)
            )
            return FusedDeviceLearner(
                net, opt, st, (8,), capacity=256, batch_size=16,
                steps_per_call=4, ingest_block=32, mesh=mesh,
            )

        fl = make(0)
        fl.add_chunk(np.ones(64, np.float32), np_chunk(64, seed=1))
        fl.ingest_staged()
        # 10 staged rows: 8 drain via the granularity decomposition, 2 stay
        # staged (< n shards) — the snapshot must carry them anyway.
        fl.add_chunk(np.ones(10, np.float32), np_chunk(10, seed=2))
        fl.ingest_staged(drain=True)
        assert fl.size == 72 and fl.staged_rows == 2
        fl.train(beta=0.4)
        sd = fl.state_dict()
        assert len(sd["staged_prio"]) == 2

        fl2 = make(9)
        fl2.load_state_dict(sd)
        assert fl2.size == 72 and fl2.staged_rows == 2
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(fl2._replay.mass)),
            np.asarray(jax.device_get(fl._replay.mass)),
        )
        m = fl2.train(beta=0.4)
        assert np.isfinite(np.asarray(m.loss)).all()


class TestSampleAheadRestampCollisions:
    def test_last_wins_per_shard_against_emulation(self):
        """Round-4 verdict item 7: sample-ahead restamps under dp>1.  Tiny
        per-shard rings force heavy duplicate sampling across the K
        batches; the final masses must equal a per-shard LAST-WINS
        emulation over the metrics' own (indices, priorities) — and no
        shard's restamp may touch another shard's rows (indices are
        shard-local by construction; global metrics columns group by
        shard)."""
        n, C_local, K, B_local = 4, 8, 6, 4
        mesh = make_mesh(num_devices=n)
        r = np.random.default_rng(3)
        mass = r.integers(1, 20, n * C_local).astype(np.float32)
        state_g, _ = _manual_global_state(mesh, n, C_local, mass)
        pre_mass = np.asarray(jax.device_get(state_g.mass)).copy()

        net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
        import optax

        opt = optax.sgd(1e-3)
        t0 = init_train_state(
            net, opt, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.uint8)
        )
        step_fn = build_train_step(
            net, opt, sync_in_step=False, grad_reduce_axis="data", jit=False
        )
        pexp = 0.6
        fused = build_sharded_fused_learn_step(
            step_fn, mesh, n * B_local, steps_per_call=K,
            priority_exponent=pexp, target_sync_freq=None,
            sample_ahead=True,
        )
        _, state_g, metrics = fused(t0, state_g, 0.5, jax.random.PRNGKey(7))
        prios = np.asarray(jax.device_get(metrics.priorities))  # [K, B]
        post = np.asarray(jax.device_get(state_g.mass))
        # Recover each shard's sampled indices by re-running the SAME
        # sampler on the shard's pre-call ring slice with the same
        # folded rng (sample-ahead draws every batch from call-entry
        # masses, so this is exact).
        idx = np.zeros((K, n * B_local), np.int64)
        for s in range(n):
            local = DeviceReplayState(
                obs=jnp.zeros((C_local, 8), jnp.uint8),
                next_obs=jnp.zeros((C_local, 8), jnp.uint8),
                action=jnp.zeros((C_local,), jnp.int32),
                reward=jnp.zeros((C_local,), jnp.float32),
                discount=jnp.zeros((C_local,), jnp.float32),
                mass=jnp.asarray(
                    pre_mass[s * C_local:(s + 1) * C_local]
                ),
                cursor=jnp.int32(0),
                count=jnp.int32(C_local),
            )
            b = device_replay_sample_many(
                local, jax.random.fold_in(jax.random.PRNGKey(7), s),
                K, B_local, 0.5,
            )
            idx[:, s * B_local:(s + 1) * B_local] = np.asarray(b.indices)
        expect = pre_mass.copy()
        # Columns [s*B_local, (s+1)*B_local) belong to shard s; index
        # values are shard-LOCAL slots.
        for s in range(n):
            cols = slice(s * B_local, (s + 1) * B_local)
            for k in range(K):
                for j_local, p in zip(idx[k, cols], prios[k, cols]):
                    g = s * C_local + int(j_local)
                    expect[g] = np.power(max(float(p), 1e-12), pexp)
        np.testing.assert_allclose(post, expect, rtol=1e-6)
        # Cross-shard isolation: rows outside each shard's sampled set
        # keep their pre-call mass.
        touched = set()
        for s in range(n):
            cols = slice(s * B_local, (s + 1) * B_local)
            touched |= {
                s * C_local + int(j) for j in idx[:, cols].reshape(-1)
            }
        untouched = [g for g in range(n * C_local) if g not in touched]
        np.testing.assert_allclose(
            post[untouched], pre_mass[untouched], rtol=0
        )


class TestAwkwardIngestMidScan:
    def test_odd_chunks_interleaved_with_trains_lose_nothing(self):
        """Ingest chunks of sizes coprime to the shard count arrive BETWEEN
        fused calls (the runtime's real cadence); exact-row accounting must
        hold across drains and a mid-stream checkpoint restore."""
        net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
        opt = make_optimizer("adam", learning_rate=1e-3)
        mesh = make_mesh(num_devices=4)

        def make(seed):
            st = init_train_state(
                net, opt, jax.random.PRNGKey(seed),
                jnp.zeros((1, 8), jnp.uint8),
            )
            return FusedDeviceLearner(
                net, opt, st, (8,), capacity=512, batch_size=16,
                steps_per_call=2, ingest_block=32, mesh=mesh,
            )

        fl = make(0)
        staged_total = 0
        sizes = [37, 51, 64, 7, 129, 3, 40]  # mostly coprime to 4
        for i, m in enumerate(sizes[:4]):
            fl.add_chunk(np.ones(m, np.float32), np_chunk(m, seed=i))
            staged_total += m
        fl.ingest_staged()
        fl.train(beta=0.4)
        fl.ingest_staged(drain=True)
        # Mid-scan snapshot (staged remainder < 4 rows rides along).
        sd = fl.state_dict()
        assert fl.size + fl.staged_rows == staged_total
        fl2 = make(1)
        fl2.load_state_dict(sd)
        assert fl2.size + fl2.staged_rows == staged_total
        for i, m in enumerate(sizes[4:]):
            fl2.add_chunk(
                np.ones(m, np.float32), np_chunk(m, seed=10 + i)
            )
            staged_total += m
            fl2.train(beta=0.4)
            fl2.ingest_staged(drain=(i == 2))
        assert fl2.size + fl2.staged_rows == staged_total
        assert fl2.staged_rows < 4  # everything drainable drained
        m = fl2.train(beta=0.4)
        assert np.isfinite(np.asarray(m.loss)).all()


SHARDED_ROW_CASES = [((6, 6, 1), np.uint8), ((5, 3), np.uint8),
                     ((5, 3), np.uint16), ((5, 3), np.float32)]


class TestShardedDedupFetchesInTheStep:
    @pytest.mark.parametrize("sample_ahead", [False, True])
    @pytest.mark.parametrize(
        "obs_shape,dtype", SHARDED_ROW_CASES,
        ids=["x".join(map(str, s)) + "-" + np.dtype(d).name
             for s, d in SHARDED_ROW_CASES])
    def test_same_bits_as_the_sharded_double_store(
            self, obs_shape, dtype, sample_ahead):
        """The sharded dedup fused step, whose scan fetches a shard's rows
        and takes them apart a step at a time, against the sharded double
        store, which gathers all K batches of observations ahead: the same
        transitions on every shard, the same key, K > 1; losses, priorities,
        masses and parameters after two calls equal to the last bit."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ape_x_dqn_tpu.replay.device_dedup import (
            DedupDeviceReplayState, RowFormat,
        )
        from ape_x_dqn_tpu.replay.device_dedup_dp import (
            build_sharded_dedup_fused_learn_step,
        )
        from test_device_dedup import assert_same_bits as same

        n, cf, c, K, B = 4, 24, 16, 3, 8
        mesh = make_mesh(num_devices=n)
        r = np.random.default_rng(11)
        frames = r.integers(0, 251, (n, cf, *obs_shape)).astype(dtype)
        ref = np.tile(np.arange(c, dtype=np.int32), n)
        nxt = np.minimum(ref + 3, cf - 1)
        shard = np.repeat(np.arange(n), c)
        small = dict(
            action=r.integers(0, 3, n * c).astype(np.int32),
            reward=r.normal(size=n * c).astype(np.float32),
            discount=np.full(n * c, 0.9, np.float32),
            mass=r.integers(1, 30, n * c).astype(np.float32),
            cursor=np.zeros(n, np.int32), count=np.full(n, c, np.int32))
        row = NamedSharding(mesh, P("data"))
        put = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda a: jax.device_put(jnp.asarray(a), row), tree)
        fmt = RowFormat.of(obs_shape, dtype)
        dd = put(DedupDeviceReplayState(
            rows=fmt.pack(frames.reshape(n * cf, *obs_shape)), fmt=fmt,
            obs_ref=ref, next_ref=nxt, fcount=np.full(n, cf, np.int32), **small))
        ds = put(DeviceReplayState(
            obs=frames[shard, ref], next_obs=frames[shard, nxt], **small))

        net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
        opt = make_optimizer("adam", learning_rate=1e-3)
        step_fn = build_train_step(
            net, opt, sync_in_step=False, grad_reduce_axis="data", jit=False)
        kw = dict(steps_per_call=K, target_sync_freq=K, sample_ahead=sample_ahead)
        fused_ds = build_sharded_fused_learn_step(step_fn, mesh, B, **kw)
        fused_dd = build_sharded_dedup_fused_learn_step(step_fn, mesh, B, **kw)
        state = lambda: jax.device_put(jax.device_get(init_train_state(  # noqa: E731
            net, opt, jax.random.PRNGKey(0), np.zeros((1, *obs_shape), dtype))),
            NamedSharding(mesh, P()))
        t_a, t_b = state(), state()
        rng = jax.random.PRNGKey(42)
        for i in range(2):
            rng, sub = jax.random.split(rng)
            t_a, ds, m_a = fused_ds(t_a, ds, 0.4, sub)
            t_b, dd, m_b = fused_dd(t_b, dd, 0.4, sub)
            assert m_b.priorities.shape == (K, B)
            same(m_a.loss, m_b.loss, f"call {i} losses")
            same(m_a.priorities, m_b.priorities, f"call {i} priorities")
            same(t_a.params, t_b.params, f"call {i} parameters")
            same(t_a.target_params, t_b.target_params, f"call {i} target")
        same(ds.mass, dd.mass, "masses")
        assert int(t_a.step) == int(t_b.step) == 2 * K
        np.testing.assert_array_equal(
            np.asarray(dd.frames), frames.reshape(n * cf, *obs_shape))


class TestRowsGatheredGradient:
    """``build_train_step(grad_reduce_axis=...)``: a dense layer whose kernel
    is large beside the batch takes its kernel's gradient from the gathered
    rows; every gradient is still the sum over the shards that one ``psum``
    over all leaves gives."""

    OBS, ACTIONS = (84, 84, 4), 5

    @staticmethod
    def _one_psum_step(net, opt, axis):
        """The reference: every parameter cast to varying, local gradients,
        all leaves summed in one ``psum`` and divided by the axis extent."""
        import optax

        from ape_x_dqn_tpu.learner.train_step import StepMetrics
        from ape_x_dqn_tpu.ops import losses
        from ape_x_dqn_tpu.types import TrainState

        def loss_fn(params, target_params, batch):
            t = batch.transition
            q = net.apply(params, t.obs)[2]
            q_next = net.apply(jax.lax.stop_gradient(params), t.next_obs)[2]
            targets = losses.double_q_target(
                q_next, net.apply(target_params, t.next_obs)[2], t.reward, t.discount)
            delta = losses.td_error(q, t.action, targets)
            return losses.td_loss(delta, batch.is_weights, kind="squared"), (delta, q)

        def step(state, batch):
            local = jax.lax.pcast(state.params, axis, to="varying")
            (loss, (delta, q)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                local, state.target_params, batch)
            n = jax.lax.axis_size(axis)
            grads = jax.tree_util.tree_map(lambda g: g / n, jax.lax.psum(grads, axis))
            updates, opt_state = opt.update(grads, state.opt_state, state.params)
            metrics = StepMetrics(
                loss=jax.lax.pmean(loss, axis),
                mean_abs_td=jax.lax.pmean(jnp.mean(jnp.abs(delta)), axis),
                max_abs_td=jax.lax.pmax(jnp.max(jnp.abs(delta)), axis),
                priorities=losses.priorities_from_td(delta, 1e-6),
                mean_q=jax.lax.pmean(jnp.mean(q), axis))
            return TrainState(
                params=optax.apply_updates(state.params, updates),
                target_params=state.target_params, opt_state=opt_state,
                step=state.step + 1, rng=state.rng), metrics

        return step

    def test_the_gathered_product_is_the_one_psums_sum(self):
        """Parameters, second moment, loss, priorities and restamped masses
        after two calls of K = 3 on four shards.  To 1e-6 relative to each
        leaf's largest value and not to the last bit: the streams' gradient
        is one product over the 16 gathered rows where the reference adds
        four products of 4 rows, so float32 rounds in another order.  SGD,
        which is linear in the gradient: RMSProp's first steps are its sign,
        and a last-bit difference at a gradient near zero flips one."""
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ape_x_dqn_tpu.models.dueling import build_network

        n, C_local, K, B = 4, 16, 3, 16
        mesh = make_mesh(num_devices=n)
        net = build_network("nature", self.ACTIONS, channels=(4, 4, 4), hidden=128,
                            compute_dtype=jnp.float32)
        opt = optax.chain(optax.clip_by_global_norm(10.0), optax.sgd(1e-2))
        t0 = init_train_state(net, opt, jax.random.PRNGKey(0),
                              jnp.zeros((1, *self.OBS), jnp.uint8))
        step = build_train_step(net, opt, loss_kind="squared", sync_in_step=False,
                                grad_reduce_axis="data", jit=False)
        r = np.random.default_rng(9)
        C = n * C_local
        ring = init_sharded_device_replay(C, self.OBS, mesh)
        ring = build_sharded_replay_add(mesh)(
            ring, jax.device_put(np_chunk(C, self.OBS, seed=3)), jnp.ones(C))
        mass = jnp.asarray(r.integers(1, 30, C), jnp.float32)

        def run(step_fn):
            fused = build_sharded_fused_learn_step(
                step_fn, mesh, B, steps_per_call=K, target_sync_freq=None)
            state = jax.jit(lambda s: s, out_shardings=NamedSharding(mesh, P()))(t0)
            replay = jax.tree_util.tree_map(jnp.copy, ring).replace(
                mass=jax.device_put(mass, ring.mass.sharding))
            out = []
            for call in range(2):
                state, replay, metrics = fused(state, replay, 0.4, jax.random.PRNGKey(call))
                out.append((metrics.loss, metrics.priorities))
            return jax.device_get((state.params, state.opt_state, out, replay.mass))

        # the streams gather (4 x 4 rows x (196 + 128) < 2 x 196 x 128), the heads do not
        fused = build_sharded_fused_learn_step(step, mesh, B, steps_per_call=K,
                                               target_sync_freq=None, jit=False)
        gathers = str(jax.make_jaxpr(fused)(
            t0, ring, 0.4, jax.random.PRNGKey(0))).count("all_gather_reduced")
        assert gathers == 4, gathers  # the input and the cotangent of two streams

        gathered, reference = run(step), run(self._one_psum_step(net, opt, "data"))
        for a, b in zip(jax.tree_util.tree_leaves(gathered),
                        jax.tree_util.tree_leaves(reference)):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * max(1e-30, np.max(np.abs(b))))
        moved = jax.tree_util.tree_map(
            lambda a, b: float(np.max(np.abs(a - np.asarray(b)))), gathered[0], t0.params)
        assert min(jax.tree_util.tree_leaves(moved)) > 0, moved
