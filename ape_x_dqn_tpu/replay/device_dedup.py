"""Frame-dedup device replay — the HBM ring storing each frame ONCE.

The double-store HBM ring (replay/device.py) carries ``obs`` AND
``next_obs`` — the 2× that made config3's 2M-slot device ring exceed a
16 GB chip (2M × 84×84 × 2 ≈ 28 GB; round-4 verdict items 1a/weakness 3).
This module is its dedup twin: a FRAME ring of ``frame_capacity``
observations plus per-transition int32 frame references, cutting the HBM
footprint to ~frame_ratio/2 of the double-store (2M slots ≈ 16.5 GB →
feasible per-chip at dp≥2 with the sharded builder in
replay/device_dedup_dp.py).

Reference addressing under XLA's int32 world:
  * frame sequence numbers live modulo ``Q = (2^30 // frame_capacity) ·
    frame_capacity`` — a multiple of the ring size, so ``slot = seq %
    frame_capacity`` stays consistent across the seq wrap, with every
    intermediate int32-safe and NO int64 anywhere in the graph.  The host
    stager keeps true int64 counters and ships refs already reduced mod Q.
  * liveness is the wrap-aware age ``(fcount − ref) mod Q ≤ frame_capacity``.
    The ingest op sweeps the whole mass vector with that test, so a
    transition whose frames were overwritten is unsampleable from the same
    program that overwrote them — the ring can never pair stale metadata
    with recycled pixels.  (Ages stay ≪ Q because the sweep runs every
    ingest; a mass-zero slot cannot resurrect — restamps only touch
    sampled slots, and dead slots are never sampled.)

Sampling/IS-weight law, batched restamp, and the K-step fused scan are
shared with the double-store via ``fused_scan_body(sample_many_fn=...)``
(replay/device.py) — the two layouts cannot drift semantically.  Equal-
semantics oracle: tests/test_device_dedup.py pins the dedup fused step
against the double-store fused step on an identical ingest stream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from ape_x_dqn_tpu.replay.device import fused_scan_body, sample_slots
from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch
from ape_x_dqn_tpu.utils.profiling import jit_fused, stage


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@struct.dataclass
class DedupDeviceReplayState:
    frames: jax.Array    # uint8 [Cf, *obs_shape] — each unique frame once
    obs_ref: jax.Array   # int32 [C] — S_t frame seq (mod Q)
    next_ref: jax.Array  # int32 [C] — S_{t+n} frame seq (mod Q)
    action: jax.Array    # int32 [C]
    reward: jax.Array    # float32 [C]
    discount: jax.Array  # float32 [C]
    mass: jax.Array      # float32 [C] — p^α, 0 marks empty/dead
    cursor: jax.Array    # int32 [] — transition ring position
    count: jax.Array     # int32 [] — transitions ever added (saturating)
    fcount: jax.Array    # int32 [] — frame seq counter (mod Q)

    @property
    def capacity(self) -> int:
        return self.mass.shape[0]

    @property
    def frame_capacity(self) -> int:
        return self.frames.shape[0]

    @property
    def seq_modulus(self) -> int:
        # Largest multiple of the ring size below 2^30: every intermediate
        # (seq + block, seq − seq) stays strictly inside int32 with no
        # silent wraparound, and the ambiguity window (Q − Cf frames
        # between sweeps before an age could alias) is still ~10^9 —
        # sweeps run every ingest, thousands of frames apart at most.
        return ((1 << 30) // self.frames.shape[0]) * self.frames.shape[0]


def init_dedup_device_replay(
    capacity: int,
    obs_shape,
    frame_capacity: int | None = None,
    frame_ratio: float = 1.25,
    obs_dtype=jnp.uint8,
) -> DedupDeviceReplayState:
    """``frame_capacity`` defaults to ``round(capacity · frame_ratio)``
    (same sizing contract as the host DedupReplay — cover the emission's
    frame/transition arrival ratio or oldest transitions die early,
    gracefully)."""
    if frame_capacity is None:
        frame_capacity = max(1, int(round(capacity * frame_ratio)))
    return DedupDeviceReplayState(
        frames=jnp.zeros((frame_capacity, *obs_shape), obs_dtype),
        obs_ref=jnp.zeros((capacity,), jnp.int32),
        next_ref=jnp.zeros((capacity,), jnp.int32),
        action=jnp.zeros((capacity,), jnp.int32),
        reward=jnp.zeros((capacity,), jnp.float32),
        discount=jnp.zeros((capacity,), jnp.float32),
        mass=jnp.zeros((capacity,), jnp.float32),
        cursor=jnp.zeros((), jnp.int32),
        count=jnp.zeros((), jnp.int32),
        fcount=jnp.zeros((), jnp.int32),
    )


def dedup_device_add_frames(
    state: DedupDeviceReplayState, frames: jax.Array
) -> DedupDeviceReplayState:
    """Append a frame block (length static).  Advances ``fcount`` mod Q;
    the liveness sweep rides the TRANSITION ingest (the op that changes
    which rows could reference overwritten frames is the frame write, but
    rows only become visible via masses — sweeping once per txn ingest
    after the paired frame blocks keeps one pass per ingest cycle; the
    runtime always ships frames-then-transitions)."""
    U = frames.shape[0]
    Cf = state.frame_capacity
    if U > Cf:
        raise ValueError(f"frame block {U} exceeds frame ring {Cf}")
    Q = state.seq_modulus
    with stage("ingest"):
        idx = ((state.fcount + jnp.arange(U, dtype=jnp.int32)) % Q) % Cf
        return state.replace(
            frames=state.frames.at[idx].set(frames),
            fcount=(state.fcount + U) % Q,
        )


def _age(state: DedupDeviceReplayState, ref: jax.Array) -> jax.Array:
    Q = state.seq_modulus
    return (state.fcount - ref) % Q


def dedup_device_add_transitions(
    state: DedupDeviceReplayState,
    obs_ref: jax.Array,      # int32 [M] absolute seqs mod Q (host-resolved)
    next_ref: jax.Array,
    action: jax.Array,
    reward: jax.Array,
    discount: jax.Array,
    priorities: jax.Array,
    priority_exponent: float = 0.6,
) -> DedupDeviceReplayState:
    """Ring-insert a transition block + the liveness sweep (one fused
    whole-vector pass: rows whose obs frame aged out of the ring get mass
    0 in the same program — see module docstring)."""
    M = priorities.shape[0]
    if M > state.capacity:
        raise ValueError(
            f"chunk of {M} transitions exceeds replay capacity {state.capacity}"
        )
    with stage("ingest"):
        idx = (state.cursor + jnp.arange(M, dtype=jnp.int32)) % state.capacity
        mass = jnp.power(jnp.maximum(priorities.astype(jnp.float32), 1e-12),
                         priority_exponent)
        new = state.replace(
            obs_ref=state.obs_ref.at[idx].set(obs_ref.astype(jnp.int32)),
            next_ref=state.next_ref.at[idx].set(next_ref.astype(jnp.int32)),
            action=state.action.at[idx].set(action.astype(jnp.int32)),
            reward=state.reward.at[idx].set(reward),
            discount=state.discount.at[idx].set(discount),
            mass=state.mass.at[idx].set(mass),
            cursor=(state.cursor + M) % state.capacity,
            count=jnp.minimum(state.count + M, jnp.int32(1 << 30)),
        )
        # Sweep: obs_ref is each row's OLDEST frame (DedupChunk layout
        # contract), so one age test invalidates exactly the frame-dead rows.
        dead = _age(new, new.obs_ref) > new.frame_capacity
        return new.replace(mass=jnp.where(dead, 0.0, new.mass))


def dedup_sample_many(
    state: DedupDeviceReplayState,
    rng: jax.Array,
    num_batches: int,
    batch_size: int,
    beta: jax.Array | float = 0.4,
    axis_name: str | None = None,
) -> PrioritizedBatch:
    """Stratified PER sample over the dedup layout — identical law and IS
    weights to ``device_replay_sample_many`` (the same ``sample_slots``);
    only the frame gather goes through the ref indirection."""
    K, B = num_batches, batch_size
    idx, weights = sample_slots(state, rng, K, B, beta, axis_name)
    idx2 = idx.reshape(K, B)
    Cf = state.frame_capacity
    with stage("gather"):
        obs = state.frames[state.obs_ref[idx] % Cf]
        next_obs = state.frames[state.next_ref[idx] % Cf]
        transition = NStepTransition(
            obs=obs.reshape(K, B, *state.frames.shape[1:]),
            action=state.action[idx2],
            reward=state.reward[idx2],
            discount=state.discount[idx2],
            next_obs=next_obs.reshape(K, B, *state.frames.shape[1:]),
        )
    return PrioritizedBatch(
        transition=transition, indices=idx2, is_weights=weights,
    )


def build_dedup_fused_learn_step(
    train_step_fn,
    batch_size: int,
    steps_per_call: int = 1,
    priority_exponent: float = 0.6,
    target_sync_freq: int | None = 2500,
    include_ingest: bool = False,
    sample_ahead: bool = False,
    jit: bool = True,
):
    """The dedup twin of ``device.build_fused_learn_step`` — same K-step
    [sample → train → restamp] scan (literally the same ``fused_scan_body``,
    parameterized by the dedup sampler), same hoisted target sync.

    ``include_ingest=True`` prepends a fixed-shape frame+transition ingest
    to each call (the bench/bulk path); the async runtime uses False and
    ingests on its own clock via the two add ops above.

    Returns (with ingest)
    ``fn(train_state, replay_state, frames, obs_ref, next_ref, action,
    reward, discount, chunk_priorities, beta, rng)`` or (without)
    ``fn(train_state, replay_state, beta, rng)``; both states donated.
    """

    def fused(train_state, replay_state, beta, rng):
        return fused_scan_body(
            train_step_fn, train_state, replay_state, beta, rng,
            steps_per_call=steps_per_call, batch_size=batch_size,
            priority_exponent=priority_exponent,
            target_sync_freq=target_sync_freq, sample_ahead=sample_ahead,
            sample_many_fn=dedup_sample_many,
        )

    if include_ingest:
        inner = fused

        def fused_ingest(train_state, replay_state, frames, obs_ref,
                         next_ref, action, reward, discount,
                         chunk_priorities, beta, rng):
            replay_state = dedup_device_add_frames(replay_state, frames)
            replay_state = dedup_device_add_transitions(
                replay_state, obs_ref, next_ref, action, reward, discount,
                chunk_priorities, priority_exponent,
            )
            return inner(train_state, replay_state, beta, rng)

        fused = fused_ingest

    if jit:
        return jit_fused(fused, donate_argnums=(0, 1))
    return fused
