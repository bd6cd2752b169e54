"""Sharded device replay: per-device HBM ring shards + fused K-step scan
under ``shard_map`` — device replay and learner data parallelism COMBINED.

Round-3 verdict, top item: the fused HBM path (replay/device.py) and the
mesh learner (parallel/dp.py) were mutually exclusive, so no configuration
could scale the 4.5k single-chip steps/s by the device count — BASELINE
config 4's 50k steps/s had no code path.  This module is that path:

  * the replay ring shards over the mesh's ``data`` axis — each device owns
    ``capacity / n`` slots in ITS HBM and ingests ``1/n`` of every actor
    chunk (leading-axis contiguous split, so each shard keeps a
    time-ordered FIFO sub-stream and ring overwrite remains eviction);
  * each fused call runs the K-step [sample → train → restamp] scan on
    every device over its OWN shard, with the gradient all-reduce
    (``pmean`` over ICI) inside the scan body — the only cross-device
    traffic is 2·|params| per step, exactly what data-parallel training
    fundamentally requires; sampling and priority restamp never leave the
    owning device;
  * sampling is stratified PER *within* each shard (B/n rows per device).
    Shards contribute equally, so the realized sampling law is
    q_i = (mass_i / shard_total) / n; the IS weights correct for exactly
    that law (device_replay_sample_many's ``axis_name`` mode) with the
    global size and a global max-normalization (``psum``/``pmax``).  With
    uniform chunk striping the shard totals track each other and the law
    converges to the single-ring p_i = mass_i / total; the weights are
    exact for the actual law either way, so the estimator stays unbiased
    (the same per-shard-PER scheme distributed replay services use).

Reference mapping: this scales the reference's single learner hot loop
(reference learner.py:63-80) the way SURVEY §7 build stage 5 prescribes —
not by translating its manager RPCs, but by putting the whole
sample/train/restamp loop inside one SPMD program per device group.

All state lives in global jax Arrays (``NamedSharding`` over the mesh), so
checkpointing device_gets one global pytree; per-shard cursors/counts ride
along as ``[n]``-shaped arrays sharded over the same axis.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ape_x_dqn_tpu.replay.device import (
    DeviceReplayState,
    device_replay_add,
    fused_scan_body,
)
from ape_x_dqn_tpu.utils.profiling import jit_fused

_AXIS = "data"


def replay_specs() -> DeviceReplayState:
    """PartitionSpec pytree for the sharded replay state: every leaf —
    rings on their slot axis, per-shard cursor/count on their only axis —
    splits over ``data``."""
    sh = P(_AXIS)
    return DeviceReplayState(
        obs=sh, next_obs=sh, action=sh, reward=sh, discount=sh, mass=sh,
        cursor=sh, count=sh,
    )


def _local(state: DeviceReplayState) -> DeviceReplayState:
    """Inside shard_map: the [1]-shaped cursor/count block → the scalar
    spelling device.py's functions expect."""
    return state.replace(cursor=state.cursor[0], count=state.count[0])


def _packed(state: DeviceReplayState) -> DeviceReplayState:
    return state.replace(cursor=state.cursor[None], count=state.count[None])


def init_sharded_device_replay(
    capacity: int,
    obs_shape,
    mesh: Mesh,
    obs_dtype=jnp.uint8,
) -> DeviceReplayState:
    """Allocate the global ring, sharded over ``data`` at creation (zeros
    materialize directly on each device — no host-side ``capacity``-sized
    array ever exists)."""
    n = mesh.shape[_AXIS]
    if capacity % n:
        raise ValueError(
            f"replay capacity {capacity} must divide by the data-axis "
            f"extent {n} (per-device ring shards)"
        )
    sh = NamedSharding(mesh, P(_AXIS))

    def init():
        return DeviceReplayState(
            obs=jnp.zeros((capacity, *obs_shape), obs_dtype),
            next_obs=jnp.zeros((capacity, *obs_shape), obs_dtype),
            action=jnp.zeros((capacity,), jnp.int32),
            reward=jnp.zeros((capacity,), jnp.float32),
            discount=jnp.zeros((capacity,), jnp.float32),
            mass=jnp.zeros((capacity,), jnp.float32),
            cursor=jnp.zeros((n,), jnp.int32),
            count=jnp.zeros((n,), jnp.int32),
        )

    shardings = DeviceReplayState(
        obs=sh, next_obs=sh, action=sh, reward=sh, discount=sh, mass=sh,
        cursor=sh, count=sh,
    )
    return jax.jit(init, out_shardings=shardings)()


def build_sharded_replay_add(
    mesh: Mesh,
    priority_exponent: float = 0.6,
    jit: bool = True,
):
    """Sharded ingest: chunk rows split contiguously over ``data`` (row
    block d of M/n goes to shard d's ring).  Chunk length must divide by
    the axis extent — the host driver enforces block granularity."""
    specs = replay_specs()

    def add(state, chunk, priorities):
        def body(st, ch, pr):
            return _packed(
                device_replay_add(_local(st), ch, pr, priority_exponent)
            )

        return shard_map(
            body, mesh=mesh,
            in_specs=(specs, P(_AXIS), P(_AXIS)),
            out_specs=specs,
        )(state, chunk, priorities)

    if jit:
        return jax.jit(add, donate_argnums=(0,))
    return add


def build_sharded_fused_learn_step(
    train_step_fn,
    mesh: Mesh,
    batch_size: int,
    steps_per_call: int = 1,
    priority_exponent: float = 0.6,
    target_sync_freq: Optional[int] = 2500,
    sample_ahead: bool = False,
    jit: bool = True,
):
    """The sharded twin of ``device.build_fused_learn_step`` (ingest
    excluded — the runtime ingests on its own clock via the sharded add).

    Args mirror the unsharded builder; ``train_step_fn`` must be built with
    ``grad_reduce_axis="data"`` and ``sync_in_step=False`` so the gradients
    are summed over the shards inside the scan body, in the step that
    applies them (``build_train_step`` says where and in what order: jax's
    own sums as the backward pass yields each leaf, a large dense kernel's
    from the gathered rows instead), and the target sync hoists to the call
    boundary.  ``batch_size`` is the GLOBAL batch; each shard samples
    ``batch_size / n`` rows from its own ring.

    Returns ``fn(train_state, replay_state, beta, rng) -> (train_state,
    replay_state, metrics)``; metrics leaves are stacked [K, ...] with
    ``priorities`` globally [K, batch_size] (sharded over ``data`` on the
    row axis); jitted with both states donated.
    """
    n = mesh.shape[_AXIS]
    if batch_size % n:
        raise ValueError(
            f"batch_size {batch_size} must divide by the data-axis extent {n}"
        )
    B_local = batch_size // n
    K = steps_per_call
    specs = replay_specs()

    def body(train_state, replay_state, beta, rng):
        # Per-shard sampling stream: every device must draw distinct rows
        # from its shard.
        rng = jax.random.fold_in(rng, jax.lax.axis_index(_AXIS))
        train_state, r, metrics = fused_scan_body(
            train_step_fn, train_state, _local(replay_state), beta, rng,
            steps_per_call=K, batch_size=B_local,
            priority_exponent=priority_exponent,
            target_sync_freq=target_sync_freq, sample_ahead=sample_ahead,
            axis_name=_AXIS,
        )
        return train_state, _packed(r), metrics

    # Metrics: scalars are pmean/pmax-reduced inside the train step →
    # replicated; per-row priorities (and sampled indices in sample-ahead
    # metrics) stay shard-local → global rows over ``data``.
    from ape_x_dqn_tpu.learner.train_step import StepMetrics

    metrics_specs = StepMetrics(
        loss=P(), mean_abs_td=P(), max_abs_td=P(),
        priorities=P(None, _AXIS), mean_q=P(),
    )
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), specs, P(), P()),
        out_specs=(P(), specs, metrics_specs),
    )
    if jit:
        # The callers commit both states to the mesh; beta and the key
        # arrive uncommitted and jit places them.
        return jit_fused(
            fn, mesh=mesh, arg_specs=(P(), specs, None, None),
            donate_argnums=(0, 1),
        )
    return fn
