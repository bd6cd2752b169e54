"""The actor fleet: batched rollouts, ε-ladder, n-step emission, priorities.

The reference runs each actor as its own OS process doing batch-1 torch
inference with a per-step ``print`` on the hot path (reference
actor.py:146-191).  That pattern can't feed a TPU learner (SURVEY §7 hard
parts #3).  The TPU-native inversion implemented here:

  * **One fleet, one forward.**  N actor envs step in lockstep
    (``SyncVectorEnv``); action selection for the whole fleet is a single
    jitted ``policy_step`` (forward + vectorized ε-greedy) — batch = N rides
    the MXU, one host↔device round trip per fleet step instead of N.
  * **ε-ladder preserved**: actor i uses ε^(1+α·i/(N−1)) (reference
    actor.py:111-114), materialized once as a device vector.
  * **Sliding-window n-step with zero extra forwards.**  The fleet keeps a
    host-side history ring of the last ``flush_every + n`` steps (obs,
    action, reward, discount, q-values).  Every ``flush_every`` steps it
    emits ``flush_every`` *overlapping* n-step transitions per actor
    (stride 1 — the paper's emission; the reference's non-overlapping
    window is stride=n, SURVEY §2 component 3) and computes initial
    priorities |R + D·max_a Q(S_{t+n}) − Q(S_t)[A_t]| (the reference's
    max-Q actor rule, actor.py:138-142) **from the q-values already computed
    during action selection** — no second forward pass.
  * Episode boundaries: per-step discount γ·(1−done) folds terminal masking
    into the return math (defect fixed vs. reference, SURVEY §2.8).
    Truncation (time limits) keeps its bootstrap, per the env contract
    (envs/core.py:24-28): a window hitting a truncation at offset k is
    emitted with ``next_obs = S_final`` (the episode's final observation,
    which never feeds the policy) and ``discount = γ^(k+1)``, so the
    LEARNER bootstraps through its live target net every time the sample
    is replayed — the return math still stops at the boundary (no window
    ever crosses into the next episode's states), and no stale
    collection-time Q is ever baked into stored rewards.

Parameter sync mirrors reference actor.py:189-191 (poll every
``sync_every`` fleet steps) against a ``ParamSource`` — any object with a
``get(current_version) -> (params, version) | None`` method (the runtime's
versioned param store, or a trivial local stub in tests).
"""

from __future__ import annotations

from typing import Callable, List, NamedTuple, Sequence

import jax
import numpy as np

from ape_x_dqn_tpu.envs.vector import SyncVectorEnv
from ape_x_dqn_tpu.ops.exploration import epsilon_greedy, epsilon_ladder
from ape_x_dqn_tpu.ops.nstep import nstep_returns_np
from ape_x_dqn_tpu.types import DedupChunk, NStepTransition


class Chunk(NamedTuple):
    """One flush: transitions + actor-computed initial priorities.

    ``transitions`` is an ``NStepTransition`` batch (dense wire format) or,
    with the fleet's ``emit_dedup=True``, a ``DedupChunk`` (each frame
    once + refs) — consumers are wired by the same config knob.
    """

    priorities: np.ndarray        # float32 [M]
    transitions: object           # NStepTransition | DedupChunk, batch M
    actor_steps: int              # fleet env steps this chunk covers


class EpisodeStat(NamedTuple):
    actor_id: int
    episode_return: float
    episode_length: int


def build_policy_step(network, seed: int = 0) -> Callable:
    """Jitted fleet policy: forward + ε-greedy in one XLA program.

    Returns ``(params, obs, epsilons, step) -> (actions, q_values)``; the
    PRNG key is derived in-graph by folding the step counter into the
    seed-derived base key, so the host passes only an int — no key
    threading, and distinct seeds give independent exploration streams.
    """

    @jax.jit
    def policy_step(params, obs, epsilons, step):
        q = network.apply(params, obs)[2]
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        actions = epsilon_greedy(rng, q, epsilons)
        return actions, q

    return policy_step


class ActorFleet:
    """N lockstep actors producing prioritized n-step chunks.

    Args:
      env_fns: one constructor per actor (reference: ``num_actors``,
        parameters.json:9).
      network: the Q-network (flax module) used for action selection.
      n_step: the n-step horizon (reference ``num_steps``=3).
      gamma: discount (reference parameters.json:14).
      epsilon/epsilon_alpha: ε-ladder parameters (reference 0.4 / 7).
      flush_every: fleet steps between chunk emissions — the analogue of the
        reference's ``n_step_transition_batch_size``=5 flush gate
        (actor.py:181-187), but measured in steps, emitting
        ``flush_every × N`` transitions per flush.
      sync_every: fleet steps between parameter-store polls (reference
        ``Q_network_sync_freq``=500, actor.py:189-191).
    """

    def __init__(
        self,
        env_fns: Sequence[Callable],
        network,
        n_step: int = 3,
        gamma: float = 0.99,
        epsilon: float = 0.4,
        epsilon_alpha: float = 7.0,
        flush_every: int = 16,
        sync_every: int = 500,
        seed: int = 0,
        epsilon_index_offset: int = 0,
        epsilon_total: int | None = None,
        emission: str = "overlapping",
        emit_dedup: bool = False,
        emit_dedup_groups: int = 1,
    ):
        self.envs = SyncVectorEnv(env_fns)
        self.network = network
        self.n_step = int(n_step)
        self.gamma = float(gamma)
        self.flush_every = int(flush_every)
        self.sync_every = int(sync_every)
        # Emission cadence: "overlapping" emits every step as a window start
        # (stride 1, the Ape-X paper's sliding window); "strided" emits only
        # n-aligned starts (stride n — the reference's non-overlapping
        # advance-by-n buffer, reference actor.py:44-70).
        if emission not in ("overlapping", "strided"):
            raise ValueError(f"unknown emission mode: {emission}")
        self.stride = self.n_step if emission == "strided" else 1
        if self.flush_every < self.stride:
            raise ValueError(
                "strided emission needs flush_every >= num_steps (a flush "
                "window shorter than the stride can contain no aligned start)"
            )
        if emit_dedup and self.flush_every < self.n_step:
            raise ValueError(
                "dedup emission needs flush_every >= num_steps — carry refs "
                "reach at most one chunk back (types.DedupChunk contract)"
            )
        N = self.envs.num_envs
        # When this fleet is one shard of a larger actor set (process-
        # parallel workers each own a slice), the ε-ladder spans the GLOBAL
        # actor count and this fleet takes rows [offset, offset+N) — actor
        # identity, and hence exploration diversity, is fleet-placement
        # independent (reference actor.py:111-114 indexes global actor ids).
        total = epsilon_total if epsilon_total is not None else N
        off = int(epsilon_index_offset)
        if off < 0 or off + N > total:
            raise ValueError(
                f"epsilon ladder slice [{off}, {off + N}) exceeds total {total}"
            )
        self._epsilons = epsilon_ladder(epsilon, epsilon_alpha, total)[off:off + N]
        self._policy_step = build_policy_step(network, seed=seed)
        self._obs = self.envs.reset(seed=seed)
        # History ring: H = flush_every + n rows; global step s lives at
        # slot s % H (rotating cursor — no per-step memmove of obs history).
        H = self.flush_every + self.n_step
        obs_shape = self.envs.observation_shape
        self._H = H
        self._hist_obs = np.zeros((H, N, *obs_shape), np.uint8)
        self._hist_action = np.zeros((H, N), np.int32)
        self._hist_reward = np.zeros((H, N), np.float32)
        self._hist_discount = np.zeros((H, N), np.float32)
        self._hist_qmax = np.zeros((H, N), np.float32)
        self._hist_qtaken = np.zeros((H, N), np.float32)
        # Truncation bookkeeping: the final observation of a time-limited
        # episode (valid only where _hist_trunc) — flushed windows point
        # their next_obs here so the learner bootstraps at train time.
        self._hist_trunc = np.zeros((H, N), bool)
        self._hist_trunc_obs = np.zeros((H, N, *obs_shape), np.uint8)
        self._rows = 0          # valid rows in history (grows to H, then stays)
        self._step_count = 0    # total fleet steps
        self.params = None
        self.param_version = -1
        # Dedup emission state (types.DedupChunk): fresh random source ids
        # per fleet INSTANCE — a respawned worker's new fleet bootstraps a
        # self-contained first chunk, so consumers never resolve carry refs
        # across an incarnation gap.  ``emit_dedup_groups`` splits the
        # fleet's actors into that many INDEPENDENT dedup streams (one
        # source each): the sharded dedup ring routes whole sources to
        # shards, so a single fleet must present >= n_shards sources or
        # some shards would starve (runtime/fused_dedup.DedupStager).
        self.emit_dedup = bool(emit_dedup)
        g = int(emit_dedup_groups)
        if g < 1:
            raise ValueError("emit_dedup_groups must be >= 1")
        if g > 1 and not emit_dedup:
            raise ValueError("emit_dedup_groups requires emit_dedup=True")
        if g > N:
            raise ValueError(
                f"emit_dedup_groups {g} exceeds the fleet's {N} actors"
            )
        import os as _os

        self._groups = g
        # Group b owns actor columns [bounds[b], bounds[b+1]).
        self._group_bounds = [round(b * N / g) for b in range(g + 1)]
        self._source = [
            int.from_bytes(_os.urandom(8), "little") >> 1 for _ in range(g)
        ]
        self._chunk_seq = [0] * g
        self._last_U = [0] * g   # previous chunk's total frame count
        self._last_bw = [0] * g  # previous chunk's base window row

    @property
    def num_actors(self) -> int:
        return self.envs.num_envs

    @property
    def step_count(self) -> int:
        """Total fleet steps taken (== per-actor env steps, lockstep)."""
        return self._step_count

    def sync_params(self, source) -> bool:
        """Poll the param source; returns True if new params were adopted.

        Snapshots arrive as host (numpy) pytrees — the store's wire format —
        and are uploaded to device once here, so the per-step policy call
        never re-transfers params.
        """
        got = source.get(self.param_version)
        if got is None:
            return False
        params, self.param_version = got
        # The old copy goes before the new one comes: beside a learner on
        # the same chip two copies of a large network do not fit.
        self.params = None
        self.params = jax.device_put(params)
        return True

    def _roll_in(self, obs, action, reward, discount, qmax, qtaken,
                 trunc=None, final_obs=None):
        """Write one fleet step at the rotating cursor slot s % H."""
        slot = self._step_count % self._H
        self._hist_obs[slot] = obs
        self._hist_action[slot] = action
        self._hist_reward[slot] = reward
        self._hist_discount[slot] = discount
        self._hist_qmax[slot] = qmax
        self._hist_qtaken[slot] = qtaken
        if trunc is None:
            self._hist_trunc[slot] = False
        else:
            self._hist_trunc[slot] = trunc
            if trunc.any():
                self._hist_trunc_obs[slot][trunc] = final_obs[trunc]
        self._rows = min(self._rows + 1, self._H)

    def _flush(self) -> List[Chunk]:
        """Emit n-step transitions per actor from the history ring (one
        chunk; ``emit_dedup_groups`` > 1 emits one DedupChunk per actor
        group) —
        window starts 0..F-1 of the flush frame (all of them overlapping
        at stride 1; the GLOBALLY n-aligned subset at stride n, the
        reference's non-overlapping emission).  Requires a full ring
        (_rows == H).

        Called after ``_step_count`` was incremented past the newest row, so
        the oldest row (global step ``_step_count − H``) lives at slot
        ``_step_count % H``; ``order`` gathers rows oldest→newest once per
        flush (amortized ~H/F rows of copy per step, vs. H rows per step for
        a shift-down ring).
        """
        n, F, N = self.n_step, self.flush_every, self.num_actors
        order = (np.arange(self._H) + self._step_count) % self._H
        # Window starts 0..F-1; start+n <= H-1 indexes stay in the ring.
        # Strided emission keeps only starts that are multiples of the
        # stride in GLOBAL step numbering (s0 = the oldest row's global
        # step), so windows stay non-overlapping across flush boundaries
        # exactly like the reference's advance-by-n buffer
        # (reference actor.py:44-70).
        starts = np.arange(F)
        if self.stride > 1:
            s0 = self._step_count - self._H
            starts = starts[(s0 + starts) % self.stride == 0]
        S = len(starts)
        rewards = self._hist_reward[order[: F + n - 1]]
        discounts = self._hist_discount[order[: F + n - 1]]
        returns, boot = nstep_returns_np(rewards, discounts, n)  # [F, N]
        returns, boot = returns[starts], boot[starts]            # [S, N]
        next_idx = order[starts + n]
        qtaken = self._hist_qtaken[order[starts]]
        boot_qmax = self._hist_qmax[next_idx]
        truncs = self._hist_trunc[order[: F + n - 1]]  # [F+n-1, N]
        # trunc_k[j, a] = offset k of the truncation that re-targets window
        # (starts[j], a)'s next_obs (−1: none) — index-level so the dense
        # and dedup materializations below share ONE branch structure.
        trunc_k = np.full((S, N), -1, np.int64)
        if truncs.any():
            # Truncation bootstrap (envs/core.py:24-28): a window whose
            # FIRST done is a truncation at offset k re-targets next_obs to
            # the episode's final observation with discount γ^(k+1); the
            # n-step return is already correct (cumulative discount zeroes
            # contributions past the boundary).  Priorities use Q(S_{t+k})
            # — the last Q computed before the final obs — as the bootstrap
            # proxy (the final obs never went through the policy net); the
            # learner restamps with the exact value on first replay.
            qmax_seq = self._hist_qmax[order[: F + n - 1]]
            alive = np.ones(boot.shape, bool)          # no done before k
            for k in range(n):
                m = alive & truncs[starts + k]
                if m.any():
                    boot[m] = self.gamma ** (k + 1)
                    trunc_k[m] = k
                    boot_qmax[m] = qmax_seq[starts + k][m]
                alive &= discounts[starts + k] != 0.0
        # Actor priority rule: |n-step TD error| with max-Q bootstrap
        # (reference actor.py:138-142), per transition (not collapsed).
        td = returns + boot * boot_qmax - qtaken
        priorities = np.abs(td).astype(np.float32)          # [S, N]
        action = self._hist_action[order[starts]]           # [S, N]
        reward = returns.astype(np.float32)
        discount = boot.astype(np.float32)
        if self.emit_dedup:
            return [
                self._build_dedup(
                    g, order, starts, trunc_k, priorities, action, reward,
                    discount,
                )
                for g in range(self._groups)
            ]
        obs = self._hist_obs[order[starts]]            # [S, N, *obs]
        next_obs = self._hist_obs[next_idx]            # [S, N, *obs]
        for k in range(n):
            m = trunc_k == k
            if m.any():
                next_obs[m] = self._hist_trunc_obs[order[starts + k]][m]
        transitions = NStepTransition(
            obs=obs.reshape(S * N, *obs.shape[2:]),
            action=action.reshape(-1),
            reward=reward.reshape(-1),
            discount=discount.reshape(-1),
            next_obs=next_obs.reshape(S * N, *next_obs.shape[2:]),
        )
        return [Chunk(priorities.reshape(-1), transitions, F * N)]

    def _build_dedup(self, g, order, starts, trunc_k, priorities, action,
                     reward, discount) -> Chunk:
        """Assemble group ``g``'s frame-dedup chunk (types.DedupChunk):
        ship only the F NEW step rows for this group's actor columns (all
        H on the group's bootstrap flush) plus truncation extras; windows
        overlapping the previous flush carry negative refs into its tail."""
        n, F = self.n_step, self.flush_every
        H = self._H
        a0, a1 = self._group_bounds[g], self._group_bounds[g + 1]
        Ng = a1 - a0
        bw = 0 if self._chunk_seq[g] == 0 else n  # first NEW window row
        rows = order[bw:H]                        # new step rows, old→new
        step_frames = self._hist_obs[rows][:, a0:a1]   # [H-bw, Ng, *obs]
        obs_shape = step_frames.shape[2:]
        S = len(starts)
        a_grid = np.broadcast_to(np.arange(Ng), (S, Ng))
        s_grid = np.broadcast_to(starts[:, None], (S, Ng))
        in_chunk = s_grid >= bw
        obs_ref = np.where(
            in_chunk,
            (s_grid - bw) * Ng + a_grid,
            # Carry: window row σ (< bw = n) was the previous chunk's
            # window row σ + F, at its step index (σ + F − prev_bw)·Ng + a;
            # negative refs are relative to the previous chunk's END.
            (s_grid + F - self._last_bw[g]) * Ng + a_grid - self._last_U[g],
        ).astype(np.int64)
        next_ref = ((s_grid + n - bw) * Ng + a_grid).astype(np.int64)
        tk = trunc_k[:, a0:a1]
        extras = []
        extra_index: dict = {}
        if (tk >= 0).any():
            for j, a in zip(*np.nonzero(tk >= 0)):
                k = int(tk[j, a])
                t_row = int(starts[j] + k)        # window row of the trunc
                key = (t_row, int(a))
                if key not in extra_index:
                    extra_index[key] = len(extras)
                    extras.append(
                        self._hist_trunc_obs[order[t_row]][a0 + a]
                    )
                next_ref[j, a] = (H - bw) * Ng + extra_index[key]
        U_step = (H - bw) * Ng
        frames = step_frames.reshape(U_step, *obs_shape)
        if extras:
            frames = np.concatenate([frames, np.stack(extras)], axis=0)
        chunk = DedupChunk(
            frames=frames,
            obs_ref=obs_ref.reshape(-1).astype(np.int32),
            next_ref=next_ref.reshape(-1).astype(np.int32),
            action=action[:, a0:a1].reshape(-1),
            reward=reward[:, a0:a1].reshape(-1),
            discount=discount[:, a0:a1].reshape(-1),
            source=self._source[g],
            chunk_seq=self._chunk_seq[g],
            prev_frames=self._last_U[g],
        )
        self._chunk_seq[g] += 1
        self._last_U[g] = frames.shape[0]
        self._last_bw[g] = bw
        return Chunk(
            priorities[:, a0:a1].reshape(-1), chunk, F * Ng
        )

    def collect(
        self,
        num_steps: int,
        param_source=None,
        selector=None,
    ) -> tuple[List[Chunk], List[EpisodeStat]]:
        """Run ``num_steps`` fleet steps; return emitted chunks + episode
        stats.  The synchronous core — the async runtime wraps this in a
        thread; the deterministic test mode calls it directly.

        ``selector`` is the central-inference seam (actor.inference=
        central; serving/central.CentralSelector): when given, action
        selection is ``selector.select(obs, step) -> (actions, q,
        param_version)`` — the fleet holds NO params, ``param_version``
        tracks the serving tier's replies, and the q rows feed the
        priority math exactly as local q values do.  Everything else
        (history ring, n-step emission, priorities, episode stats) is
        identical in both modes.
        """
        if selector is None and self.params is None:
            if param_source is None or not self.sync_params(param_source):
                raise RuntimeError(
                    "ActorFleet has no params — call sync_params or pass param_source"
                )
        chunks: List[Chunk] = []
        stats: List[EpisodeStat] = []
        for _ in range(num_steps):
            if selector is not None:
                actions, q, version = selector.select(
                    self._obs, self._step_count
                )
                actions = np.asarray(actions)
                q = np.asarray(q)
                self.param_version = int(version)
            else:
                # One transfer for both outputs: each device round trip
                # costs fixed latency, so the fleet batch size — not the
                # per-actor work — sets the FPS ceiling.
                actions, q = jax.device_get(self._policy_step(
                    self.params, self._obs, self._epsilons, self._step_count
                ))
            vs = self.envs.step(actions)
            done = vs.terminated | vs.truncated
            discount = (self.gamma * (1.0 - done)).astype(np.float32)
            # Truncation: record the episode's final observation (vs.obs —
            # the next policy input is vs.reset_obs, so this frame is
            # otherwise lost).  _flush points truncated windows' next_obs at
            # it with discount γ^(k+1), so the learner bootstraps with its
            # LIVE target net on every replay — baking a collection-time Q
            # into the reward would freeze a stale estimate in the buffer
            # for the slot's whole lifetime.
            trunc = vs.truncated & ~vs.terminated
            self._roll_in(
                self._obs,
                actions,
                vs.reward,
                discount,
                q.max(axis=-1),
                np.take_along_axis(q, actions[:, None], axis=-1)[:, 0],
                trunc=trunc,
                final_obs=vs.obs,
            )
            self._obs = vs.reset_obs
            self._step_count += 1
            for i in np.nonzero(~np.isnan(vs.episode_return))[0]:
                stats.append(
                    EpisodeStat(int(i), float(vs.episode_return[i]), int(vs.episode_length[i]))
                )
            # Flush on ring-fill, then every flush_every steps after — this
            # phase alignment emits every global step as a window start
            # exactly once (flushing on step % flush_every instead would
            # silently drop the first few steps whenever n % flush_every != 0).
            if (
                self._rows == self._H
                and (self._step_count - self._H) % self.flush_every == 0
            ):
                chunks.extend(self._flush())
            if param_source is not None and self._step_count % self.sync_every == 0:
                self.sync_params(param_source)
        return chunks, stats


class LocalParamSource:
    """Trivial in-process param source for tests and the single-process
    driver — the analogue of the reference's manager dict
    (main.py:38, actor.py:106) without the serialization.

    Snapshots are stored as host numpy pytrees (``jax.device_get`` at
    publish).  This is load-bearing, not just the wire format: the learner's
    train step donates its state buffers, so publishing live device arrays
    would hand actors references that die on the next update.
    """

    def __init__(self, params=None):
        self._params = jax.device_get(params) if params is not None else None
        self._version = 0 if params is not None else -1

    def publish(self, params):
        self._params = jax.device_get(params)
        self._version += 1

    def get(self, current_version: int):
        if self._params is None or self._version <= current_version:
            return None
        return self._params, self._version
