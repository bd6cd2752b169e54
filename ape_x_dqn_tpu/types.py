"""Transition schema and train-state pytrees.

The reference duplicates two namedtuples (``Transition``/``N_Step_Transition``)
by copy-paste across three files (reference: actor.py:11-12, learner.py:8,
replay.py:5).  Here the wire format is a single set of ``flax.struct`` pytrees
shared by every subsystem, so they move through ``jit``/``pjit`` and across
host threads without conversion.  There is deliberately no 1-step transition
type: the actor pool composes n-step windows from its history ring and only
``NStepTransition`` ever crosses a subsystem boundary.

Design notes (TPU-first):
  * Observations are stored ``uint8`` end-to-end and cast to compute dtype
    only inside the jitted step — HBM bandwidth and replay RAM are the
    bottleneck, not FLOPs.
  * Replay identity is an integer slot index, not the reference's string key
    ``str(actor_id)+str(seq_num)`` (reference: actor.py:47) — string keys force
    O(N) scans (reference: replay.py:54-56); indices make priority updates
    O(log N) in the sum-tree.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

Array = jax.Array
PyTree = Any

# The flax collection a network's layers sow per-call counts in (an expert
# layer's pairs per expert).  The train step asks every network for it; what a
# network makes of it is the network's (``routing_metrics``, ``rebalanced``).
ROUTING = "routing"


@struct.dataclass
class NStepTransition:
    """An n-step transition (reference actor.py:12 ``N_Step_Transition``).

    ``reward`` is the accumulated n-step return R_{t→t+n}; ``discount`` is the
    *correct* bootstrap factor γ^n with terminal masking (the reference stores
    γ^(n−1) and never masks — SURVEY §2.8), so the learner target is simply
    ``reward + discount * bootstrap`` with no special cases.
    """

    obs: Array          # uint8 [*obs_shape]        — S_t
    action: Array       # int32 []                  — A_t
    reward: Array       # float32 []                — R_{t→t+n}
    discount: Array     # float32 []                — prod_k γ·(1−done_k), 0 past terminal
    next_obs: Array     # uint8 [*obs_shape]        — S_{t+n}

    @property
    def batch_shape(self):
        return self.action.shape


class DedupChunk(NamedTuple):
    """An actor flush with each frame stored ONCE — the frame-dedup wire
    format (round-4 verdict item 1a: the double-store's ``obs`` +
    ``next_obs`` is a 2× tax on RAM, ingest bandwidth, snapshots and HBM).

    ``frames`` holds the flush's unique observations; each transition
    references its S_t / S_{t+n} by index.  Refs are relative to THIS
    chunk's first frame: ``r >= 0`` → ``frames[r]``; ``r < 0`` → frame
    ``prev_frames + r`` of this source's PREVIOUS chunk (the n-row overlap
    between consecutive sliding windows — consecutive chunks share their
    boundary frames, so steady-state frame traffic is ~1 frame per
    transition instead of 2).  Consumers resolve refs against a per-source
    frame counter; a gap in ``chunk_seq`` (dropped/reordered chunk, worker
    respawn) invalidates carry refs, and consumers drop just the carried
    rows (≤ n·num_actors once per gap).

    Layout contract (producers): frames are ordered [step-row-major, then
    truncation extras]; ``obs_ref < next_ref`` row-wise (liveness checks
    use ``obs_ref`` as each row's oldest frame).
    """

    frames: np.ndarray     # uint8 [U, *obs_shape] — each unique frame once
    obs_ref: np.ndarray    # int32 [M] — S_t ref (may be negative: carry)
    next_ref: np.ndarray   # int32 [M] — S_{t+n} ref (>= 0 always)
    action: np.ndarray     # int32 [M]
    reward: np.ndarray     # float32 [M] — n-step return
    discount: np.ndarray   # float32 [M] — bootstrap factor
    source: int            # producer identity (fresh per fleet incarnation)
    chunk_seq: int         # per-source monotone flush counter
    prev_frames: int       # U of this source's previous chunk (carry check)

    @property
    def batch_shape(self):
        return self.action.shape


def materialize_dedup(chunk: DedupChunk, prev: DedupChunk | None = None):
    """Decode a DedupChunk (plus its predecessor, for carry refs) back to a
    dense NStepTransition — the test oracle for emission equivalence and
    the fallback for consumers that want the dense wire format."""
    neg = chunk.obs_ref < 0
    if neg.any():
        if prev is None:
            raise ValueError("chunk has carry refs but no previous chunk")
        if prev.frames.shape[0] != chunk.prev_frames:
            raise ValueError("previous chunk size mismatch for carry refs")
        carry_idx = np.clip(chunk.prev_frames + chunk.obs_ref,
                            0, chunk.prev_frames - 1)
        obs = np.where(
            neg[(...,) + (None,) * (chunk.frames.ndim - 1)],
            prev.frames[carry_idx],
            chunk.frames[np.clip(chunk.obs_ref, 0, None)],
        )
    else:
        obs = chunk.frames[chunk.obs_ref]
    return NStepTransition(
        obs=obs,
        action=chunk.action,
        reward=chunk.reward,
        discount=chunk.discount,
        next_obs=chunk.frames[chunk.next_ref],
    )


@struct.dataclass
class PrioritizedBatch:
    """A replay sample as fed to the learner: transitions + sampling metadata."""

    transition: NStepTransition
    indices: Array      # int32 [B] — replay slot ids, echoed back for priority update
    is_weights: Array   # float32 [B] — importance-sampling weights (β-annealed)


@struct.dataclass
class TrainState:
    """Full learner state: one pytree, one checkpoint, one donation unit.

    Covers everything the reference fails to checkpoint (reference
    learner.py:18-23 restores only the online net): params, target params,
    optimizer state, step counter and PRNG key.
    """

    params: PyTree
    target_params: PyTree
    opt_state: PyTree
    step: Array         # int32 []
    rng: Array          # PRNGKey


def host_stack(transitions):
    """Stack a list of same-structure pytrees into one batched pytree (numpy).

    Host-side helper for the actor→replay path; stays off the device.
    """
    leaves = [jax.tree_util.tree_leaves(t) for t in transitions]
    treedef = jax.tree_util.tree_structure(transitions[0])
    stacked = [np.stack([l[i] for l in leaves]) for i in range(len(leaves[0]))]
    return jax.tree_util.tree_unflatten(treedef, stacked)


def tree_slice(tree: PyTree, idx) -> PyTree:
    """Index every leaf of a batched pytree (host or device)."""
    return jax.tree_util.tree_map(lambda x: x[idx], tree)
