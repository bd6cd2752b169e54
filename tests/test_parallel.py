"""Distributed-semantics tests on 8 virtual CPU devices (SURVEY §4 level 4:
pjit sharding + collectives without hardware — conftest.py forces
xla_force_host_platform_device_count=8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ape_x_dqn_tpu.learner.train_step import (
    build_train_step,
    init_train_state,
    make_optimizer,
)
from ape_x_dqn_tpu.models.dueling import DuelingMLP, build_network
from ape_x_dqn_tpu.parallel import (
    build_sharded_train_step,
    infer_param_sharding,
    make_mesh,
    place_batch,
    shard_train_state,
)
from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch


def make_batch(B, obs_shape=(12,), num_actions=3, seed=0):
    r = np.random.default_rng(seed)
    return PrioritizedBatch(
        transition=NStepTransition(
            obs=r.integers(0, 255, (B, *obs_shape), dtype=np.uint8),
            action=r.integers(0, num_actions, (B,), dtype=np.int32),
            reward=r.normal(size=(B,)).astype(np.float32),
            discount=np.full((B,), 0.95, np.float32),
            next_obs=r.integers(0, 255, (B, *obs_shape), dtype=np.uint8),
        ),
        indices=np.arange(B, dtype=np.int32),
        is_weights=np.ones((B,), np.float32),
    )


def make_state_and_net(num_actions=3, obs_shape=(12,), hidden=(32, 32), seed=0):
    net = DuelingMLP(num_actions=num_actions, hidden_sizes=hidden)
    opt = make_optimizer("adam", learning_rate=1e-3)
    state = init_train_state(
        net, opt, jax.random.PRNGKey(seed), jnp.zeros((1, *obs_shape), jnp.uint8)
    )
    return net, opt, state


def test_eight_virtual_devices_present():
    assert len(jax.devices()) == 8


def test_mesh_shapes():
    mesh = make_mesh()
    assert mesh.shape == {"data": 8, "model": 1}
    mesh = make_mesh(model_parallel=2)
    assert mesh.shape == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        make_mesh(num_devices=6, model_parallel=4)
    with pytest.raises(ValueError):
        make_mesh(num_devices=16)


def test_dp_step_matches_single_device():
    """The mesh-sharded step must be numerically equivalent to the
    single-device fused step (same params, same batch)."""
    net, opt, state = make_state_and_net()
    batch = make_batch(32)

    single_step = build_train_step(net, opt, target_sync_freq=10)
    s1, m1 = single_step(state, jax.device_put(batch))

    _, _, state2 = make_state_and_net()  # fresh, identical init (same seed)
    mesh = make_mesh()
    dp_step, sharded_state = build_sharded_train_step(
        net, opt, mesh, state2, batch, target_sync_freq=10
    )
    s2, m2 = dp_step(sharded_state, place_batch(batch, mesh))

    assert np.isclose(float(m1.loss), float(m2.loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(m1.priorities), np.asarray(m2.priorities), rtol=1e-4, atol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s2.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_dp_multiple_steps_stay_in_sync():
    net, opt, state = make_state_and_net()
    mesh = make_mesh()
    batch = make_batch(64)
    dp_step, sharded_state = build_sharded_train_step(net, opt, mesh, state, batch)
    for i in range(5):
        sharded_state, metrics = dp_step(
            sharded_state, place_batch(make_batch(64, seed=i), mesh)
        )
    assert int(sharded_state.step) == 5
    assert np.isfinite(float(metrics.loss))
    # Replicated leaves really are replicated (one shard each device).
    leaf = jax.tree_util.tree_leaves(sharded_state.params)[0]
    assert leaf.sharding.is_fully_replicated


def test_model_axis_shards_wide_kernels():
    net, opt, state = make_state_and_net(hidden=(512, 512))
    mesh = make_mesh(model_parallel=2)
    shardings = infer_param_sharding(state.params, mesh)
    specs = {
        path[-2].key if len(path) >= 2 else str(path): sh.spec
        for (path, sh) in jax.tree_util.tree_leaves_with_path(shardings)
    }
    # At least one wide dense kernel sharded over the model axis.
    assert any(spec == P(None, "model") for spec in specs.values()), specs
    # Train step still runs and matches the replicated result.
    batch = make_batch(32)
    dp_step, sharded_state = build_sharded_train_step(net, opt, mesh, state, batch)
    s2, m2 = dp_step(sharded_state, place_batch(batch, mesh))
    single = build_train_step(net, opt)
    _, _, state_b = make_state_and_net(hidden=(512, 512))
    s1, m1 = single(state_b, jax.device_put(batch))
    assert np.isclose(float(m1.loss), float(m2.loss), rtol=1e-4)


def test_conv_network_dp_step():
    """The flagship conv net through the sharded step on a 2D mesh."""
    net = build_network("conv", 4)
    opt = make_optimizer("rmsprop")
    obs_shape = (84, 84, 1)
    state = init_train_state(
        net, opt, jax.random.PRNGKey(0), jnp.zeros((1, *obs_shape), jnp.uint8)
    )
    mesh = make_mesh(model_parallel=2)
    batch = make_batch(16, obs_shape=obs_shape, num_actions=4)
    dp_step, sharded_state = build_sharded_train_step(net, opt, mesh, state, batch)
    new_state, metrics = dp_step(sharded_state, place_batch(batch, mesh))
    assert np.isfinite(float(metrics.loss))
    assert int(new_state.step) == 1


def test_async_pipeline_data_parallel_end_to_end():
    """learner.data_parallel=4 runs the WHOLE async runtime — actor thread,
    host replay, prefetch infeed, sharded train step, priority write-back,
    param publish — over a 4-device mesh."""
    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.num_actors = 4
    cfg.actor.T = 100_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 16
    cfg.learner.data_parallel = 4
    cfg.learner.min_replay_mem_size = 128
    cfg.learner.publish_every = 10
    cfg.learner.optimizer = "adam"
    cfg.replay.capacity = 4096
    pipe = AsyncPipeline(cfg, log_every=100)
    assert pipe.mesh is not None and pipe.mesh.shape["data"] == 4
    # The live train state is actually sharded over the mesh.
    leaf = jax.tree_util.tree_leaves(pipe.comps.state.params)[0]
    assert len(leaf.sharding.device_set) == 4
    result = pipe.run(learner_steps=120, warmup_timeout=120.0)
    assert result["step"] >= 120
    assert np.isfinite(result["learner/loss"])  # key must exist: NaN fails
    assert result["param_version"] > 1
    # Priorities made it back from the sharded step into the host replay.
    assert pipe.comps.replay.size() > 0
