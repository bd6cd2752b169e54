"""Sharded frame-dedup device replay: per-device dedup ring shards + the
fused K-step scan under ``shard_map`` — the configuration that makes
config3's 2M-slot replay FEASIBLE per chip (round-4 verdict item 1a:
2M × 1.25 rows of 7,168 B ≈ 17.9 GB global ≈ 4.5 GB/chip at dp=4, vs the
double-store's 28 GB that OOMed a 16 GB chip).

Structure mirrors replay/device_dp.py (the double-store sharded ring) with
one routing difference: transitions gather their frames BY REFERENCE, so a
transition must live on the same shard as its frames.  Chunks therefore
route WHOLE to one shard (the host stager pins each SOURCE to a shard —
carry refs resolve against the previous chunk of the same source, which
round-robin-by-chunk would scatter) instead of striping rows.  Each shard
keeps an independent frame-seq space; per-shard stratified PER with the
same realized-law IS correction as device_dp (shards contribute equally).

All state lives in global jax Arrays (NamedSharding over the mesh);
per-shard cursor/count/fcount ride along as [n]-shaped arrays.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ape_x_dqn_tpu.replay.device import fused_scan_body
from ape_x_dqn_tpu.replay.device_dedup import (
    DedupDeviceReplayState,
    RowFormat,
    dedup_device_add_frames,
    dedup_device_add_transitions,
    dedup_fetch,
    dedup_sample_slots,
)
from ape_x_dqn_tpu.utils.profiling import jit_fused

_AXIS = "data"


def dedup_replay_specs() -> DedupDeviceReplayState:
    sh = P(_AXIS)
    return DedupDeviceReplayState(
        frames=sh, obs_ref=sh, next_ref=sh, action=sh, reward=sh,
        discount=sh, mass=sh, cursor=sh, count=sh, fcount=sh,
    )


def _local(state: DedupDeviceReplayState) -> DedupDeviceReplayState:
    return state.replace(
        cursor=state.cursor[0], count=state.count[0], fcount=state.fcount[0]
    )


def _packed(state: DedupDeviceReplayState) -> DedupDeviceReplayState:
    return state.replace(
        cursor=state.cursor[None], count=state.count[None],
        fcount=state.fcount[None],
    )


def init_sharded_dedup_replay(
    capacity: int,
    obs_shape,
    mesh: Mesh,
    frame_capacity: int | None = None,
    frame_ratio: float = 1.25,
    obs_dtype=jnp.uint8,
) -> DedupDeviceReplayState:
    n = mesh.shape[_AXIS]
    if frame_capacity is None:
        frame_capacity = max(n, int(round(capacity * frame_ratio)))
        frame_capacity -= frame_capacity % n
    if capacity % n or frame_capacity % n:
        raise ValueError(
            f"capacity {capacity} and frame_capacity {frame_capacity} must "
            f"divide by the data-axis extent {n} (per-device ring shards)"
        )
    sh = NamedSharding(mesh, P(_AXIS))

    fmt = RowFormat.of(obs_shape, obs_dtype)

    def init():
        return DedupDeviceReplayState(
            rows=fmt.zeros(frame_capacity), fmt=fmt,
            obs_ref=jnp.zeros((capacity,), jnp.int32),
            next_ref=jnp.zeros((capacity,), jnp.int32),
            action=jnp.zeros((capacity,), jnp.int32),
            reward=jnp.zeros((capacity,), jnp.float32),
            discount=jnp.zeros((capacity,), jnp.float32),
            mass=jnp.zeros((capacity,), jnp.float32),
            cursor=jnp.zeros((n,), jnp.int32),
            count=jnp.zeros((n,), jnp.int32),
            fcount=jnp.zeros((n,), jnp.int32),
        )

    shardings = dedup_replay_specs()
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), shardings,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.jit(init, out_shardings=shardings)()


def shard_seq_modulus(frame_capacity: int, n: int) -> int:
    """The per-shard seq modulus the host stager must reduce refs by
    (each shard's LOCAL frame ring is frame_capacity / n)."""
    cf = frame_capacity // n
    return ((1 << 30) // cf) * cf


def build_sharded_dedup_add_frames(mesh: Mesh, jit: bool = True):
    """Per-shard frame-block ingest: ``frames`` is [n, B_f, *obs] with
    shard d consuming ITS OWN block frames[d] (chunks route whole to a
    shard — module docstring)."""
    specs = dedup_replay_specs()

    def add(state, frames):
        def body(st, fr):
            return _packed(dedup_device_add_frames(_local(st), fr[0]))

        return shard_map(
            body, mesh=mesh, in_specs=(specs, P(_AXIS)), out_specs=specs,
        )(state, frames)

    if jit:
        return jax.jit(add, donate_argnums=(0,))
    return add


def build_sharded_dedup_add_transitions(
    mesh: Mesh, priority_exponent: float = 0.6, jit: bool = True
):
    """Per-shard transition-block ingest (+ the liveness sweep, per
    shard): every leading-axis-[n] argument carries shard d's own block."""
    specs = dedup_replay_specs()

    def add(state, obs_ref, next_ref, action, reward, discount, priorities):
        def body(st, o, nx, a, r, d, p):
            return _packed(dedup_device_add_transitions(
                _local(st), o[0], nx[0], a[0], r[0], d[0], p[0],
                priority_exponent,
            ))

        row = P(_AXIS)
        return shard_map(
            body, mesh=mesh,
            in_specs=(specs, row, row, row, row, row, row),
            out_specs=specs,
        )(state, obs_ref, next_ref, action, reward, discount, priorities)

    if jit:
        return jax.jit(add, donate_argnums=(0,))
    return add


def build_sharded_dedup_fused_learn_step(
    train_step_fn,
    mesh: Mesh,
    batch_size: int,
    steps_per_call: int = 1,
    priority_exponent: float = 0.6,
    target_sync_freq: Optional[int] = 2500,
    sample_ahead: bool = False,
    jit: bool = True,
):
    """The sharded dedup twin of ``device_dp.build_sharded_fused_learn_step``
    — same contract (global batch, per-shard B/n sampling, the gradients
    summed over the shards inside the scan by a step built with
    ``grad_reduce_axis="data"``: where and in what order is
    ``build_train_step``'s to say), dedup gather."""
    n = mesh.shape[_AXIS]
    if batch_size % n:
        raise ValueError(
            f"batch_size {batch_size} must divide by the data-axis extent {n}"
        )
    B_local = batch_size // n
    K = steps_per_call
    specs = dedup_replay_specs()

    def body(train_state, replay_state, beta, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(_AXIS))
        train_state, r, metrics = fused_scan_body(
            train_step_fn, train_state, _local(replay_state), beta, rng,
            steps_per_call=K, batch_size=B_local,
            priority_exponent=priority_exponent,
            target_sync_freq=target_sync_freq, sample_ahead=sample_ahead,
            axis_name=_AXIS, sample_many_fn=dedup_sample_slots,
            fetch_fn=dedup_fetch,
        )
        return train_state, _packed(r), metrics

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
