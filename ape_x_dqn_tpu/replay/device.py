"""Device-resident prioritized replay — sample, train, and restamp in-graph.

The host replay (replay/buffer.py) re-ships a frame batch host→device on
every learner step.  This module keeps the whole buffer in HBM as a pytree of
jax arrays, so after an actor chunk crosses the host→device boundary *once*,
everything else — ring insert, stratified prioritized sampling, IS weights,
the train step, and the priority write-back — runs inside XLA programs with
zero further transfers.  ``build_fused_learn_step`` goes further and fuses
ingest + K train steps into ONE dispatch (`lax.scan` over sampled batches),
amortizing host dispatch overhead — the single-chip path to the north-star
steps/sec (SURVEY §7 hard parts #1-2 collapse into on-device ops).

Sampling is a two-level prefix-sum inverse-CDF (ops/pallas/sampling.py), not
a pointer-chasing tree: row sums, a small cumsum over them and one cumsum of
the picked rows are bandwidth-bound passes that the VPU eats; an O(log N)
tree walk would serialize on exactly the hardware that hates it.  Same math as the host sum-tree: mass ∝ p^α,
stratified targets, β-annealed IS weights (reference replay.py:24-30
semantics, reference defects excluded per SURVEY §2.8).

All mutating functions are functional (state in, state out) and meant to be
jitted with donation so ring writes happen in place in HBM.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ape_x_dqn_tpu.ops.pallas.sampling import sample_indices
from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch
from ape_x_dqn_tpu.utils.profiling import jit_fused, stage


@struct.dataclass
class DeviceReplayState:
    obs: jax.Array          # uint8 [C, *obs_shape]
    next_obs: jax.Array     # uint8 [C, *obs_shape]
    action: jax.Array       # int32 [C]
    reward: jax.Array       # float32 [C]
    discount: jax.Array     # float32 [C]
    mass: jax.Array         # float32 [C] — p^α, 0 marks an empty slot
    cursor: jax.Array       # int32 []
    count: jax.Array        # int32 [] — total ever added (saturating view: size = min(count, C))

    @property
    def capacity(self) -> int:
        return self.mass.shape[0]


def init_device_replay(capacity: int, obs_shape, obs_dtype=jnp.uint8) -> DeviceReplayState:
    return DeviceReplayState(
        obs=jnp.zeros((capacity, *obs_shape), obs_dtype),
        next_obs=jnp.zeros((capacity, *obs_shape), obs_dtype),
        action=jnp.zeros((capacity,), jnp.int32),
        reward=jnp.zeros((capacity,), jnp.float32),
        discount=jnp.zeros((capacity,), jnp.float32),
        mass=jnp.zeros((capacity,), jnp.float32),
        cursor=jnp.zeros((), jnp.int32),
        count=jnp.zeros((), jnp.int32),
    )


def device_replay_add(
    state: DeviceReplayState,
    transitions: NStepTransition,
    priorities: jax.Array,
    priority_exponent: float = 0.6,
) -> DeviceReplayState:
    """Ring-insert a chunk (batch M static).  FIFO overwrite == eviction,
    and the slot's mass is replaced — no stale-priority leak."""
    M = priorities.shape[0]
    if M > state.capacity:
        # A chunk wider than the ring would wrap idx onto itself, and XLA
        # scatter with duplicate indices has unspecified write order —
        # silent ring corruption.  Static shapes make this a build-time
        # check (mirrors PrioritizedReplay.add's host-side guard).
        raise ValueError(
            f"chunk of {M} transitions exceeds replay capacity {state.capacity}"
        )
    with stage("ingest"):
        idx = (state.cursor + jnp.arange(M, dtype=jnp.int32)) % state.capacity
        mass = jnp.power(jnp.maximum(priorities.astype(jnp.float32), 1e-12),
                         priority_exponent)
        return state.replace(
            obs=state.obs.at[idx].set(transitions.obs),
            next_obs=state.next_obs.at[idx].set(transitions.next_obs),
            action=state.action.at[idx].set(
                transitions.action.astype(jnp.int32)),
            reward=state.reward.at[idx].set(transitions.reward),
            discount=state.discount.at[idx].set(transitions.discount),
            mass=state.mass.at[idx].set(mass),
            cursor=(state.cursor + M) % state.capacity,
            count=state.count + M,
        )


def device_replay_sample(
    state: DeviceReplayState,
    rng: jax.Array,
    batch_size: int,
    beta: jax.Array | float = 0.4,
    axis_name: str | None = None,
) -> PrioritizedBatch:
    """Stratified proportional sample with IS weights, fully on device.

    The K=1 case of ``device_replay_sample_many`` (single implementation —
    the strict-PER path and the sample-ahead path cannot diverge)."""
    batch = device_replay_sample_many(state, rng, 1, batch_size, beta, axis_name)
    return jax.tree_util.tree_map(lambda a: a[0], batch)


def device_replay_sample_many(
    state: DeviceReplayState,
    rng: jax.Array,
    num_batches: int,
    batch_size: int,
    beta: jax.Array | float = 0.4,
    axis_name: str | None = None,
) -> PrioritizedBatch:
    """Sample K stratified batches from the *current* priorities in one
    batched inverse-CDF call + one row gather (leaves get leading [K, B]).

    The per-step spelling is almost all fixed op overhead, not bandwidth,
    because a 32-row sample launches ~15 tiny ops.  Batching all K batches
    into one call amortizes that overhead K-fold.  Memory: the gather materializes all K batches —
    K·B·2·obs_bytes of transient HBM (K=2048, B=32, 84×84×1 ≈ 0.9 GB;
    frame-stacked 84×84×4 ≈ 3.7 GB) — so size K to the observation shape;
    the strict path holds one batch at a time.  The trade: batches 2..K are
    drawn from priorities
    as of call entry rather than after each preceding step's restamp — K
    steps of staleness, the same order the async Ape-X pipeline already
    tolerates between actor-priority computation and learner restamp
    (reference's actors/learner run fully desynchronized).

    ``axis_name``: when called per-shard inside ``shard_map`` (replay/
    device_dp.py — each device samples ``batch_size`` rows from its OWN
    ring shard), the IS weights must correct for the *actual* sampling
    law: row i of shard s is drawn with q_i = (mass_i / shard_total) / n
    (shards contribute equally, proportional within a shard), so
    w_i = (N_global · q_i)^-β, normalized by the **global** batch max
    (``pmax`` over the axis).  With ``None`` this reduces to the
    single-ring law exactly.
    """
    K, B = num_batches, batch_size
    idx, weights = sample_slots(state, rng, K, B, beta, axis_name)
    idx2 = idx.reshape(K, B)
    with stage("gather"):
        transition = NStepTransition(
            obs=state.obs[idx].reshape(K, B, *state.obs.shape[1:]),
            action=state.action[idx2],
            reward=state.reward[idx2],
            discount=state.discount[idx2],
            next_obs=state.next_obs[idx].reshape(K, B, *state.next_obs.shape[1:]),
        )
    return PrioritizedBatch(
        transition=transition, indices=idx2, is_weights=weights,
    )


def sample_slots(
    state, rng: jax.Array, num_batches: int, batch_size: int, beta, axis_name
) -> Tuple[jax.Array, jax.Array]:
    """The ``sample`` stage, one spelling for both ring layouts (it reads
    ``mass``, ``count`` and ``capacity`` alone): the stratified inverse-CDF
    draw of K·B slots from the current masses and their importance weights
    under the law ``device_replay_sample_many`` documents.  Returns
    (idx int32 [K·B], weights float32 [K, B])."""
    K, B = num_batches, batch_size
    with stage("sample"):
        total = jnp.sum(state.mass)
        bounds = total / B
        u = jax.random.uniform(rng, (K, B))
        targets = (jnp.arange(B, dtype=jnp.float32)[None, :] + u) * bounds
        targets = jnp.minimum(targets, total * (1.0 - 1e-7))
        idx = sample_indices(state.mass, targets.reshape(-1))      # [K*B]
        size_i = jnp.maximum(jnp.minimum(state.count, state.capacity), 1)
        idx = jnp.minimum(idx, size_i - 1)  # a zero-mass tail is never drawn
        probs = state.mass[idx] / jnp.maximum(total, 1e-12)
        if axis_name is None:
            n_shards = 1
            size_global = size_i
        else:
            n_shards = jax.lax.psum(1, axis_name)
            size_global = jax.lax.psum(size_i, axis_name)
        weights = jnp.power(
            jnp.maximum(
                size_global.astype(jnp.float32) * probs / n_shards, 1e-12
            ),
            -beta,
        ).reshape(K, B)
        wmax = jnp.max(weights, axis=1, keepdims=True)
        if axis_name is not None:
            wmax = jax.lax.pmax(wmax, axis_name)
        return idx, (weights / wmax).astype(jnp.float32)


def device_replay_restamp_last(
    state: DeviceReplayState,
    indices: jax.Array,     # int32 [K, B] in step order
    priorities: jax.Array,  # float32 [K, B]
    priority_exponent: float = 0.6,
) -> DeviceReplayState:
    """Batched priority restamp with sequential (last-wins) semantics.

    A slot sampled by several of the K batches must end with the *latest*
    step's priority — what K in-scan scatters would produce.  XLA scatter
    leaves duplicate-index write order unspecified, so resolve duplicates
    first: stable-sort by slot (ties keep step order), keep only each run's
    last element, and route the rest to a dummy slot that is sliced off.
    One sort + one scatter replaces K 32-element scatters of pure op
    overhead.
    """
    idx = indices.reshape(-1)
    mass = jnp.power(
        jnp.maximum(priorities.astype(jnp.float32).reshape(-1), 1e-12),
        priority_exponent,
    )
    order = jnp.argsort(idx, stable=True)
    si, sm = idx[order], mass[order]
    is_last = jnp.concatenate(
        [si[1:] != si[:-1], jnp.ones((1,), bool)]
    )
    target = jnp.where(is_last, si, state.capacity)  # dummy slot C
    ext = jnp.concatenate([state.mass, jnp.zeros((1,), jnp.float32)])
    ext = ext.at[target].set(sm)
    return state.replace(mass=ext[:-1])


def device_replay_update_priorities(
    state: DeviceReplayState,
    indices: jax.Array,
    priorities: jax.Array,
    priority_exponent: float = 0.6,
) -> DeviceReplayState:
    mass = jnp.power(jnp.maximum(priorities.astype(jnp.float32), 1e-12),
                     priority_exponent)
    return state.replace(mass=state.mass.at[indices].set(mass))


def fused_scan_body(
    train_step_fn,
    train_state,
    replay_state: DeviceReplayState,
    beta,
    rng: jax.Array,
    *,
    steps_per_call: int,
    batch_size: int,
    priority_exponent: float,
    target_sync_freq: int | None,
    sample_ahead: bool,
    axis_name: str | None = None,
    sample_many_fn=None,
    fetch_fn=None,
):
    """The K-step [sample → train → restamp] scan + hoisted target sync —
    the ONE body shared by the single-device builder below, the sharded
    builder (replay/device_dp.py, where it runs per shard inside shard_map
    with ``axis_name="data"`` and a per-shard batch size), and the
    frame-dedup layouts (replay/device_dedup.py; restamp/update only touch
    ``.mass``, which every layout carries).

    A layout is two functions.  ``sample_many_fn(state, rng, K, B, beta,
    axis_name)`` returns what it sampled, leaves ``[K, B, ...]`` with
    ``.indices``; ``fetch_fn(state, one)`` makes a step's slice of that
    (leaves ``[B, ...]``) the step's ``PrioritizedBatch``, inside the scan's
    body, from the ring the body closes over: with ``sample_ahead`` what
    crosses into the scan is what was sampled, and the batch-sized work is
    done a step at a time.  With no ``fetch_fn`` the slice is the batch (the
    double store: its rows are its observations)."""
    K, B = steps_per_call, batch_size
    step_before = train_state.step
    if sample_many_fn is None:
        sample_many_fn = device_replay_sample_many
    if fetch_fn is None:
        fetch_fn = lambda r_state, one: one  # noqa: E731

    if sample_ahead:
        sampled = sample_many_fn(
            replay_state, rng, K, B, beta, axis_name
        )

        def body_pre(t_state, one):
            t_state, metrics = train_step_fn(t_state, fetch_fn(replay_state, one))
            return t_state, metrics

        train_state, metrics = jax.lax.scan(body_pre, train_state, sampled)
        with stage("restamp"):
            replay_state = device_replay_restamp_last(
                replay_state, sampled.indices, metrics.priorities,
                priority_exponent,
            )
    else:

        def body(carry, step_rng):
            t_state, r_state = carry
            batch = fetch_fn(r_state, jax.tree_util.tree_map(
                lambda a: a[0],
                sample_many_fn(r_state, step_rng, 1, B, beta, axis_name),
            ))
            t_state, metrics = train_step_fn(t_state, batch)
            with stage("restamp"):
                r_state = device_replay_update_priorities(
                    r_state, batch.indices, metrics.priorities,
                    priority_exponent,
                )
            return (t_state, r_state), metrics

        rngs = jax.random.split(rng, K)
        (train_state, replay_state), metrics = jax.lax.scan(
            body, (train_state, replay_state), rngs
        )
    if target_sync_freq is not None:
        with stage("target_sync"):
            crossed = (train_state.step // target_sync_freq) > (
                step_before // target_sync_freq
            )
            train_state = train_state.replace(
                target_params=jax.tree_util.tree_map(
                    lambda online, target: jnp.where(
                        crossed, online.astype(target.dtype), target
                    ),
                    train_state.params,
                    train_state.target_params,
                )
            )
    return train_state, replay_state, metrics


def build_fused_learn_step(
    train_step_fn,
    batch_size: int,
    steps_per_call: int = 1,
    priority_exponent: float = 0.6,
    target_sync_freq: int | None = 2500,
    include_ingest: bool = True,
    sample_ahead: bool = False,
    jit: bool = True,
):
    """Fuse [ingest chunk] → scan_K [sample → train → restamp] into one
    XLA program.

    Args:
      train_step_fn: the *unjitted* fused train step
        (``build_train_step(..., jit=False)``).  When ``target_sync_freq``
        is set here, build it with ``sync_in_step=False`` — the per-step
        target-pytree rewrite costs ~95 µs/step on a v5e and is pure waste
        between the every-``freq``-step syncs.
      batch_size: replay sample size per learner step (static).
      steps_per_call: K learner steps per dispatch; host overhead amortizes
        by K (the chunk ingest happens once per call).
      target_sync_freq: hoisted target sync — after the K-step scan, copy
        online → target params iff the scan crossed a multiple of ``freq``.
        Exact when ``freq % K == 0`` (the crossing lands on a call
        boundary); otherwise the sync lands at the first boundary after the
        crossing, ≤ K−1 steps late — noise next to Ape-X's 2500-step
        staleness.  ``None`` = the train step handles sync itself
        (``sync_in_step=True``).

      include_ingest: with True (default) each call ingests one chunk
        before the scan — one dispatch total, the bench/bulk path, and the
        overlapped pipeline's folded-ingest dispatch
        (``FusedDeviceLearner.train_with_ingest`` builds this variant and
        rides one full ``ingest_block`` inside each fused call; the add is
        sequenced before the scan in the same program, so it is bit-for-bit
        identical to a separate ``device_replay_add`` dispatch — pinned by
        tests/test_pipeline_overlap.py).  With
        False the signature drops ``chunk``/``chunk_priorities`` and the
        caller ingests at its own cadence via ``device_replay_add`` — the
        async runtime's shape, where actor chunks arrive on their own clock.
      sample_ahead: with True, all K batches are sampled + gathered in ONE
        batched call from call-entry priorities and restamps are applied as
        one batched last-wins scatter after the scan — the per-step fixed
        op overhead is paid once per call.  Batches 2..K see priorities
        up to K steps stale (see ``device_replay_sample_many``); with False,
        each scan step samples/restamps against live priorities (the strict
        sequential-PER mode, also the test oracle for this one).

    Returns ``fn(train_state, replay_state, chunk, chunk_priorities, beta,
    rng) -> (train_state, replay_state, metrics)`` (without the chunk args
    when ``include_ingest=False``) with metrics stacked [K, ...]; jitted
    with both states donated.
    """

    def fused(train_state, replay_state, chunk, chunk_priorities, beta, rng):
        if include_ingest:
            replay_state = device_replay_add(
                replay_state, chunk, chunk_priorities, priority_exponent
            )
        return fused_scan_body(
            train_step_fn, train_state, replay_state, beta, rng,
            steps_per_call=steps_per_call, batch_size=batch_size,
            priority_exponent=priority_exponent,
            target_sync_freq=target_sync_freq, sample_ahead=sample_ahead,
        )

    if not include_ingest:
        inner = fused

        def fused_no_ingest(train_state, replay_state, beta, rng):
            return inner(train_state, replay_state, None, None, beta, rng)

        fused = fused_no_ingest

    if jit:
        return jit_fused(fused, donate_argnums=(0, 1))
    return fused
