"""The system under test, built from a configuration file: the program's
own network, optimizer, train step, fused learner and replay ring, through
the builders its runtime uses (runtime/components.py, runtime/fused_dedup.py,
bench.py).  Nothing here computes a metric."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

AXIS = "data"


def seed_key(seed: int):
    """A key from any whole number, past 32 signed bits too."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_mesh(cfg: dict):
    n = int(cfg.get("data_parallel", 1))
    if n == 1:
        return None
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(f"data_parallel={n} needs {n} devices, jax found {len(devs)}")
    return Mesh(np.array(devs[:n]), (AXIS,))


def build_learner(cfg: dict):
    """(network, optimizer, unjitted train step) as the configuration states."""
    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step, make_optimizer, with_float32_master,
    )
    from ape_x_dqn_tpu.models.dueling import build_network

    prec = cfg["precision"]
    net = build_network(
        cfg["network"], cfg["num_actions"], channels=tuple(cfg["channels"]),
        hidden=cfg["hidden"], compute_dtype=jnp.dtype(prec["compute"]),
        param_dtype=jnp.dtype(prec["params"]),
    )
    opt = make_optimizer(
        cfg["optimizer"], learning_rate=cfg["learning_rate"],
        rmsprop_decay=cfg["rmsprop_decay"], rmsprop_eps=cfg["rmsprop_eps"],
        max_grad_norm=cfg["max_grad_norm"],
        second_moment_dtype=jnp.dtype(prec["second_moment"]),
    )
    if prec["params"] == "bfloat16":
        opt = with_float32_master(opt)
    step_fn = build_train_step(
        net, opt, loss_kind=cfg["loss"], sync_in_step=False, jit=False,
        grad_reduce_axis=AXIS if int(cfg.get("data_parallel", 1)) > 1 else None,
    )
    return net, opt, step_fn


def init_state(cfg: dict, net, opt, key, mesh):
    from ape_x_dqn_tpu.learner.train_step import init_train_state

    state = init_train_state(
        net, opt, key, jnp.zeros((1, *cfg["obs_shape"]), jnp.uint8),
        target_dtype=jnp.dtype(cfg["precision"]["target_params"]),
    )
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        state = jax.device_put(jax.device_get(state), NamedSharding(mesh, P()))
    return state


def target_sync_for_calls(cfg: dict) -> int:
    """The configuration's sync period, moved to a call boundary as bench.py
    does: a multiple of K, or K where K is larger."""
    k, f = cfg["steps_per_call"], cfg["target_sync_freq"]
    return f - f % k if k <= f else k


def build_fused(cfg: dict, step_fn, mesh):
    """The fused K-step program of the configuration's replay layout."""
    kw = dict(
        steps_per_call=cfg["steps_per_call"],
        priority_exponent=cfg["priority_exponent"],
        target_sync_freq=target_sync_for_calls(cfg),
        sample_ahead=cfg["sample_ahead"],
    )
    layout = cfg["replay_layout"]
    if layout == "double_store" and mesh is None:
        from ape_x_dqn_tpu.replay.device import build_fused_learn_step

        return build_fused_learn_step(step_fn, cfg["batch_size"], include_ingest=True, **kw)
    if layout == "dedup" and mesh is None:
        from ape_x_dqn_tpu.replay.device_dedup import build_dedup_fused_learn_step

        return build_dedup_fused_learn_step(step_fn, cfg["batch_size"], **kw)
    if layout == "dedup":
        from ape_x_dqn_tpu.replay.device_dedup_dp import (
            build_sharded_dedup_fused_learn_step,
        )

        return build_sharded_dedup_fused_learn_step(step_fn, mesh, cfg["batch_size"], **kw)
    raise ValueError(f"no fused learner for replay_layout={layout!r} with mesh={mesh}")


def program_name(jitted) -> str:
    """The name a jitted function's runs carry in the device trace."""
    fn = getattr(jitted, "__wrapped__", jitted)
    return "jit_" + getattr(fn, "__name__", "fused")


def state_from_inputs(cfg: dict, opt, inputs: dict, mesh):
    """The program's train state holding weights, target weights and second
    moment made by ``correctness.make_inputs`` rather than its own."""
    from ape_x_dqn_tpu.types import TrainState

    from correctness import to_program_params

    prec = cfg["precision"]
    own = lambda t: jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), t)  # noqa: E731
    params = own(to_program_params(inputs["weights"], jnp.dtype(prec["params"])))  # donated
    nu0 = jax.tree_util.tree_leaves(inputs["nu"])[0].ravel()[0]
    opt_state = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.full_like(x, nu0)
        if any("nu" in str(p) for p in path) else x,
        opt.init(params),
    )
    state = TrainState(
        params=params,
        target_params=own(to_program_params(
            inputs["target"], jnp.dtype(prec["target_params"]))),
        opt_state=opt_state, step=jnp.zeros((), jnp.int32), rng=jax.random.PRNGKey(0))
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        state = jax.device_put(jax.device_get(state), NamedSharding(mesh, P()))
    return state
