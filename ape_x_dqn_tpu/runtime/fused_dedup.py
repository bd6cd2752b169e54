"""Host driver for the frame-dedup device replay (HBM dedup ring + fused
K-step scan) — the dedup twin of runtime/fused_learner.FusedDeviceLearner,
same duck-typed interface (add_chunk / ingest_staged / train / state_dict /
load_state_dict / size / staged_rows / params_for_publish), so the async
pipeline and checkpoint layer drive either without knowing which.

Staging here is two streams instead of one: actors ship DedupChunks
(frames + refs); the stager resolves refs to ABSOLUTE per-shard frame
sequence numbers (int64 host counters, reduced mod the device's int32-safe
Q only at ship time), pins each source to a shard (carry refs must resolve
on the device that holds the previous chunk's frames), and ships
fixed-size FRAME blocks before the TRANSITION blocks that reference them
(a transition block is eligible only when every frame it references has
landed).  Thread discipline matches FusedDeviceLearner: actor threads only
stage; all device work happens on the single train() caller.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.learner.train_step import build_train_step
from ape_x_dqn_tpu.types import DedupChunk, TrainState
from ape_x_dqn_tpu.utils import profiling

_TXN_FIELDS = ("obs_seq", "next_seq", "action", "reward", "discount", "prio")


class _ShardStage:
    """One shard's staged streams (frames + ref-resolved transitions)."""

    def __init__(self):
        self.fbuf: list = []          # frame arrays, stage order
        self.f_rows = 0               # staged frame rows not yet shipped
        self.fseq = 0                 # next absolute frame seq to assign
        self.shipped_f = 0            # frames already on the device
        # Transition chunks: dict of arrays + max_ref (eligibility gate).
        self.tbuf: list = []
        self.t_rows = 0


class DedupStager:
    """Ref resolution + per-shard block scheduling (host side, pure numpy).

    Mirrors the host DedupReplay's carry semantics exactly: per-source
    (chunk_seq, base, U) continuity records; a gap drops only the carried
    rows (``dropped_carry``)."""

    def __init__(self, n_shards: int = 1):
        from ape_x_dqn_tpu.replay.dedup import CarryResolver

        self.n = int(n_shards)
        self.shards = [_ShardStage() for _ in range(self.n)]
        # Carry resolution is per SHARD (each shard is an independent frame
        # seq space) — the same resolver the host DedupReplay uses.
        self.resolvers = [CarryResolver() for _ in range(self.n)]
        self.shard_of: dict = {}      # src -> pinned shard
        self._rr = 0

    @property
    def dropped_carry(self) -> int:
        return sum(r.dropped_carry for r in self.resolvers)

    @property
    def sources(self) -> dict:
        """src -> (shard, chunk_seq, base, U) — the combined view."""
        out = {}
        for i, r in enumerate(self.resolvers):
            for src, (seq, base, U) in r.sources.items():
                if self.shard_of.get(src) == i:
                    out[src] = (i, seq, base, U)
        return out

    def add_chunk(self, priorities: np.ndarray, chunk: DedupChunk) -> int:
        """Stage one chunk; returns transition rows accepted."""
        shard_i = self.shard_of.get(chunk.source)
        fresh = shard_i is None
        if fresh:
            shard_i = self._rr % self.n
            self._rr += 1
            self.shard_of[chunk.source] = shard_i
        st = self.shards[shard_i]
        base = st.fseq
        obs_seq, next_seq, keep = self.resolvers[shard_i].resolve(
            chunk, base
        )
        if fresh and len(self.shard_of) > 2 * 4096 * self.n:
            # Prune pins whose source record the resolvers have already
            # evicted (dead fleets).  AFTER resolve(), so the source just
            # pinned is in its resolver's live set and keeps its pin —
            # pruning first would unpin it and drop its next chunk's
            # carry rows despite a contiguous stream (round-5 review).
            live = set()
            for r in self.resolvers:
                live |= set(r.sources)
            self.shard_of = {
                s: sh for s, sh in self.shard_of.items() if s in live
            }
        U = chunk.frames.shape[0]
        st.fbuf.append(np.asarray(chunk.frames))
        st.f_rows += U
        st.fseq = base + U
        m = int(keep.sum())
        if m:
            st.tbuf.append({
                "obs_seq": obs_seq[keep],
                "next_seq": next_seq[keep],
                "action": np.asarray(chunk.action, np.int32)[keep],
                "reward": np.asarray(chunk.reward, np.float32)[keep],
                "discount": np.asarray(chunk.discount, np.float32)[keep],
                "prio": np.asarray(priorities, np.float32)[keep],
                # Eligibility gate: every ref < shipped frame count.
                "max_ref": int(next_seq[keep].max()),
            })
            st.t_rows += m
        return m

    # ---- block extraction ------------------------------------------

    def frame_blocks_available(self, block: int) -> int:
        return min(s.f_rows // block for s in self.shards)

    def take_frame_block(self, block: int) -> np.ndarray:
        """[n, block, *obs] — one block per shard (call only when
        frame_blocks_available >= 1)."""
        out = []
        for s in self.shards:
            rows, need = [], block
            while need:
                head = s.fbuf[0]
                if head.shape[0] <= need:
                    rows.append(head)
                    need -= head.shape[0]
                    s.fbuf.pop(0)
                else:
                    rows.append(head[:need])
                    s.fbuf[0] = head[need:]
                    need = 0
            s.f_rows -= block
            s.shipped_f += block
            out.append(np.concatenate(rows) if len(rows) > 1 else rows[0])
        return np.stack(out)

    def _eligible_rows(self, s: _ShardStage) -> int:
        rows = 0
        for c in s.tbuf:
            if c["max_ref"] >= s.shipped_f:
                break
            rows += len(c["prio"])
        return rows

    def txn_blocks_available(self, block: int) -> int:
        return min(self._eligible_rows(s) // block for s in self.shards)

    def take_txn_block(self, block: int) -> dict:
        """{field: [n, block] array} — one eligible block per shard."""
        out = {f: [] for f in _TXN_FIELDS}
        for s in self.shards:
            need = block
            acc = {f: [] for f in _TXN_FIELDS}
            while need:
                head = s.tbuf[0]
                k = len(head["prio"])
                if k <= need:
                    for f in _TXN_FIELDS:
                        acc[f].append(head[f])
                    need -= k
                    s.tbuf.pop(0)
                else:
                    for f in _TXN_FIELDS:
                        acc[f].append(head[f][:need])
                        head[f] = head[f][need:]
                    need = 0
            s.t_rows -= block
            for f in _TXN_FIELDS:
                out[f].append(
                    np.concatenate(acc[f]) if len(acc[f]) > 1 else acc[f][0]
                )
        return {f: np.stack(v) for f, v in out.items()}

    @property
    def staged_rows(self) -> int:
        return sum(s.t_rows for s in self.shards)

    # ---- snapshot ----------------------------------------------------

    def state_dict(self) -> dict:
        out = {"n_shards": self.n}
        for i, s in enumerate(self.shards):
            out[f"s{i}_frames"] = (
                np.concatenate(s.fbuf) if s.fbuf
                else np.zeros((0,), np.uint8)
            )
            out[f"s{i}_fseq"] = s.fseq
            out[f"s{i}_shipped_f"] = s.shipped_f
            for f in _TXN_FIELDS:
                out[f"s{i}_{f}"] = (
                    np.concatenate([c[f] for c in s.tbuf]) if s.tbuf
                    else np.zeros((0,))
                )
            out[f"s{i}_maxref"] = np.array(
                [c["max_ref"] for c in s.tbuf], np.int64
            )
            out[f"s{i}_rows"] = np.array(
                [len(c["prio"]) for c in s.tbuf], np.int64
            )
            out[f"s{i}_dropped"] = self.resolvers[i].dropped_carry
            ids, rows = self.resolvers[i].state_arrays()
            out[f"s{i}_src_ids"] = ids
            out[f"s{i}_src_state"] = rows
        src = self.shard_of
        out["shard_of_ids"] = np.array(list(src.keys()), np.int64)
        out["shard_of_vals"] = np.array(list(src.values()), np.int64)
        out["rr"] = self._rr
        return out

    def load_state_dict(self, state: dict) -> None:
        if int(state["n_shards"]) != self.n:
            raise ValueError(
                f"stager snapshot has {int(state['n_shards'])} shards, "
                f"configured {self.n}"
            )
        for i, s in enumerate(self.shards):
            fr = state[f"s{i}_frames"]
            s.fbuf = [fr] if fr.shape[0] else []
            s.f_rows = int(fr.shape[0])
            s.fseq = int(state[f"s{i}_fseq"])
            s.shipped_f = int(state[f"s{i}_shipped_f"])
            s.tbuf, s.t_rows = [], 0
            rows = state[f"s{i}_rows"]
            maxref = state[f"s{i}_maxref"]
            off = 0
            for j, k in enumerate(rows):
                k = int(k)
                c = {
                    f: state[f"s{i}_{f}"][off:off + k]
                    for f in _TXN_FIELDS
                }
                c["max_ref"] = int(maxref[j])
                s.tbuf.append(c)
                s.t_rows += k
                off += k
            self.resolvers[i].dropped_carry = int(state[f"s{i}_dropped"])
            self.resolvers[i].load_state_arrays(
                state[f"s{i}_src_ids"], state[f"s{i}_src_state"]
            )
        self.shard_of = {
            int(a): int(v)
            for a, v in zip(state["shard_of_ids"], state["shard_of_vals"])
        }
        self._rr = int(state["rr"])


class FusedDedupLearner:
    """Owns the dedup device replay + train state; drives fused K-step
    calls.  Interface-compatible with FusedDeviceLearner (the runtime and
    checkpoint layers are agnostic); ``mesh`` switches to the sharded ring
    (replay/device_dedup_dp.py) with sources pinned per shard."""

    def __init__(
        self,
        network,
        optimizer,
        state: TrainState,
        obs_shape,
        capacity: int,
        batch_size: int = 32,
        steps_per_call: int = 128,
        ingest_block: int = 256,
        priority_exponent: float = 0.6,
        target_sync_freq: int = 2500,
        loss_kind: str = "huber",
        sample_ahead: bool = False,
        frame_ratio: float = 1.25,
        mesh=None,
    ):
        from ape_x_dqn_tpu.replay.device_dedup import (
            build_dedup_fused_learn_step,
            dedup_device_add_frames,
            dedup_device_add_transitions,
            init_dedup_device_replay,
        )

        self._capacity = int(capacity)
        self._batch_size = int(batch_size)
        self.steps_per_call = int(steps_per_call)
        self._ingest_block = int(ingest_block)
        self._mesh = mesh
        self._prio_exp = priority_exponent
        step_kwargs = dict(
            loss_kind=loss_kind, sync_in_step=False, jit=False
        )
        if mesh is None:
            self._n_shards = 1
            self._state = state
            with profiling.launch.span("ring_make"):
                self._replay = init_dedup_device_replay(
                    capacity, obs_shape, frame_ratio=frame_ratio
                )
            self._seq_mod = self._replay.seq_modulus
            step_fn = build_train_step(network, optimizer, **step_kwargs)
            self._fused = build_dedup_fused_learn_step(
                step_fn, batch_size, steps_per_call=self.steps_per_call,
                priority_exponent=priority_exponent,
                target_sync_freq=target_sync_freq,
                sample_ahead=sample_ahead,
            )
            _af = jax.jit(dedup_device_add_frames, donate_argnums=(0,))
            _at = jax.jit(
                lambda st, o, nx, a, r, d, p: dedup_device_add_transitions(
                    st, o, nx, a, r, d, p, priority_exponent
                ),
                donate_argnums=(0,),
            )
            self._add_frames = lambda st, fr: _af(st, jnp.asarray(fr[0]))
            self._add_txns = lambda st, blk: _at(
                st,
                jnp.asarray(blk["obs_seq"][0] % self._seq_mod, jnp.int32),
                jnp.asarray(blk["next_seq"][0] % self._seq_mod, jnp.int32),
                jnp.asarray(blk["action"][0]),
                jnp.asarray(blk["reward"][0]),
                jnp.asarray(blk["discount"][0]),
                jnp.asarray(blk["prio"][0]),
            )
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ape_x_dqn_tpu.replay.device_dedup_dp import (
                build_sharded_dedup_add_frames,
                build_sharded_dedup_add_transitions,
                build_sharded_dedup_fused_learn_step,
                init_sharded_dedup_replay,
                shard_seq_modulus,
            )

            n = mesh.shape["data"]
            self._n_shards = n
            # Host round trip, not device_put/identity-jit on the device
            # arrays: the fused call donates this state so an aliased
            # placement would free the caller's copy, and an identity jit
            # can't rebuffer arrays COMMITTED to one device (the
            # checkpoint-restore path places them so).  Init-time only.
            self._state = jax.device_put(
                jax.device_get(state), NamedSharding(mesh, P())
            )
            with profiling.launch.span("ring_make"):
                self._replay = init_sharded_dedup_replay(
                    capacity, obs_shape, mesh, frame_ratio=frame_ratio
                )
            self._seq_mod = shard_seq_modulus(
                self._replay.frame_capacity, n
            )
            step_fn = build_train_step(
                network, optimizer, grad_reduce_axis="data", **step_kwargs
            )
            self._fused = build_sharded_dedup_fused_learn_step(
                step_fn, mesh, batch_size,
                steps_per_call=self.steps_per_call,
                priority_exponent=priority_exponent,
                target_sync_freq=target_sync_freq,
                sample_ahead=sample_ahead,
            )
            _af = build_sharded_dedup_add_frames(mesh)
            _at = build_sharded_dedup_add_transitions(
                mesh, priority_exponent
            )
            row = NamedSharding(mesh, P("data"))
            place = lambda a: jax.device_put(np.asarray(a), row)  # noqa: E731
            self._add_frames = lambda st, fr: _af(st, place(fr))
            self._add_txns = lambda st, blk: _at(
                st,
                place((blk["obs_seq"] % self._seq_mod).astype(np.int32)),
                place((blk["next_seq"] % self._seq_mod).astype(np.int32)),
                place(blk["action"]),
                place(blk["reward"]),
                place(blk["discount"]),
                place(blk["prio"]),
            )
        # self._state's rng, not the caller's: under a mesh the state
        # was re-placed replicated above — a restored state's rng arrives
        # COMMITTED to one device and would conflict with the mesh call.
        self._rng = jax.random.fold_in(self._state.rng, 0x5EED)
        self._stager = DedupStager(self._n_shards)
        # learner.ingest_block is the TOTAL rows per ingest dispatch
        # (FusedDeviceLearner contract); the stager takes per-shard blocks.
        if self._ingest_block % self._n_shards:
            raise ValueError(
                f"ingest_block {self._ingest_block} must divide by the "
                f"data-axis extent {self._n_shards}"
            )
        self._ingest_block //= self._n_shards
        # The actor threads' appends against the learner thread's takes.
        self._lock = threading.Lock()
        self._size = 0
        # Incremental-checkpoint mark (utils/checkpoint_inc): per-shard
        # ingest/ship progress at the last snapshot.  Both counters are
        # HOST-side monotone ints (every shard ingests identical block
        # rows; the stager's shipped_f is the true frame count the device
        # ring's mod-Q fcount wraps), so computing the dirty spans needs
        # NO device read — the learner thread only dispatches the span
        # gathers and the writer thread does the device_get.
        self._ckpt = None  # (ingested_rows_per_shard, (shipped_f per shard))

    # ------------------------------------------------------------- sinks

    def add_chunk(self, priorities: np.ndarray, transitions: DedupChunk):
        if not isinstance(transitions, DedupChunk):
            raise TypeError(
                "FusedDedupLearner consumes DedupChunks — build fleets with "
                "emit_dedup=True (config replay.dedup wires both ends)"
            )
        with self._lock:
            self._stager.add_chunk(
                np.asarray(priorities, np.float32), transitions
            )

    @property
    def size(self) -> int:
        return min(self._size, self._capacity)

    @property
    def staged_rows(self) -> int:
        with self._lock:
            return self._stager.staged_rows

    @property
    def state(self) -> TrainState:
        return self._state

    @state.setter
    def state(self, new_state: TrainState):
        self._state = new_state

    @property
    def step(self) -> int:
        return int(np.asarray(self._state.step))

    def params_for_publish(self):
        return self._state.params

    # ------------------------------------------------------------- learner

    def ingest_staged(self, drain: bool = False) -> int:
        """Ship staged frame blocks, then eligible transition blocks, in
        fixed ``ingest_block`` units.  Learner-thread only (the adds donate
        the ring).  A transition block is only carved once every frame it
        references has been carved ahead of it, so dispatch order (FIFO)
        preserves the frames-before-transitions invariant.  ``drain=True``
        additionally carves power-of-2 sub-blocks of the tails; whatever
        remains (transitions whose frames are still host-side) stays
        staged and rides the snapshot.  Returns transition rows ingested."""
        m = self._ingest_block
        st = self._stager
        blocks: list = []
        with self._lock:
            for kind, available, take in (
                ("f", st.frame_blocks_available, st.take_frame_block),
                ("t", st.txn_blocks_available, st.take_txn_block),
            ):
                # Full blocks, then on a drain the tail in maximal
                # power-of-2 sub-blocks (static shapes: at most
                # log2(ingest_block) jit variants, cached).
                b = m
                while b >= 1:
                    while available(b) >= 1:
                        blocks.append((kind, take(b)))
                    if not drain:
                        break
                    b >>= 1
        rows = 0
        for kind, block in blocks:
            if kind == "f":
                self._replay = self._add_frames(self._replay, block)
            else:
                self._replay = self._add_txns(self._replay, block)
                rows += block["prio"].shape[1] * self._n_shards
        self._size += rows
        return rows

    # -- snapshot (checkpointing) ----------------------------------------

    def state_dict(self) -> dict:
        r = jax.device_get(self._replay)
        out = {
            "dedup": np.asarray(True),
            "frames": r.frames, "obs_ref": r.obs_ref,
            "next_ref": r.next_ref, "action": r.action,
            "reward": r.reward, "discount": r.discount, "mass": r.mass,
            "cursor": np.asarray(r.cursor), "count": np.asarray(r.count),
            "fcount": np.asarray(r.fcount),
        }
        with self._lock:
            stage = self._stager.state_dict()
        for k, v in stage.items():
            out[f"stage_{k}"] = v
        return out

    # -- incremental snapshot (utils/checkpoint_inc delta protocol) -------

    def _chain_now(self):
        """(ingested rows per shard, shipped frames per shard) — host-side
        monotone progress counters (see the _ckpt comment in __init__)."""
        return (self._size // self._n_shards,
                tuple(s.shipped_f for s in self._stager.shards))

    def delta_state_dict(self, force_base: bool = False) -> dict:
        """Base or per-shard dirty-span delta.  The learner thread only
        computes span indices (host ints) and DISPATCHES the gathers
        (jnp.take — new device buffers, immune to the fused call's
        donation); np.asarray materialization is the writer thread's job.
        The mass vector rides whole each delta (the fused scan restamps
        arbitrary rows; at 4 bytes/slot it is noise next to the frame
        spans), as does the staged-chunk state (bounded by ingest cadence).
        Must run on the train()-caller thread, like every device op here.
        """
        import jax.numpy as jnp

        n = self._n_shards
        C_local = self._capacity // n
        Cf_global = self._replay.frame_capacity
        Cf_local = Cf_global // n
        with self._lock:
            ing_now, shipped_now = self._chain_now()
            prev = self._ckpt
        new_rows = ing_now - (prev[0] if prev else 0)
        f_new = [
            shipped_now[d] - (prev[1][d] if prev else 0)
            for d in range(n)
        ]
        if (force_base or prev is None or new_rows >= C_local
                or max(f_new) >= Cf_local):
            # ing/shipped only advance on this (the learner) thread, so the
            # full snapshot below cannot drift from the mark taken here.
            out = self.state_dict()
            out["chain_mark"] = np.asarray([ing_now, *shipped_now], np.int64)
            with self._lock:
                self._ckpt = (ing_now, shipped_now)
            return out
        ing_prev, shipped_prev = prev
        with self._lock:
            # Transition span: every shard ingests identical block rows, so
            # one local window maps to all shards.
            local = (ing_prev + np.arange(new_rows)) % C_local
            tidx = np.concatenate(
                [d * C_local + local for d in range(n)]
            ).astype(np.int32) if new_rows else np.zeros(0, np.int32)
            fidx = np.concatenate([
                d * Cf_local
                + (shipped_prev[d] + np.arange(f_new[d])) % Cf_local
                for d in range(n)
            ]).astype(np.int32) if sum(f_new) else np.zeros(0, np.int32)
            stage = self._stager.state_dict()
            self._ckpt = (ing_now, shipped_now)
        r = self._replay
        ti = jnp.asarray(tidx)
        fi = jnp.asarray(fidx)
        out = {
            "delta": np.asarray(True),
            "dedup": np.asarray(True),
            "n_shards": n,
            "chain_prev": np.asarray([ing_prev, *shipped_prev], np.int64),
            "chain_mark": np.asarray([ing_now, *shipped_now], np.int64),
            "txn_gidx": tidx,
            "txn_obs_ref": jnp.take(r.obs_ref, ti, axis=0),
            "txn_next_ref": jnp.take(r.next_ref, ti, axis=0),
            "txn_action": jnp.take(r.action, ti, axis=0),
            "txn_reward": jnp.take(r.reward, ti, axis=0),
            "txn_discount": jnp.take(r.discount, ti, axis=0),
            "frame_gidx": fidx,
            # Logical rows, whatever the ring stores: a delta written
            # before the ring was stored in packed rows applies after.
            "frame_rows": r.fmt.unpack(jnp.take(r.rows, fi, axis=0)),
            "mass": jnp.copy(r.mass),
            # Counters recomputed host-side — bit-identical to the device's
            # mod-C / saturating / mod-Q arithmetic, no device sync needed.
            "cursor": np.asarray(
                [ing_now % C_local] * n, np.int32
            ),
            "count": np.asarray(
                [min(ing_now, 1 << 30)] * n, np.int32
            ),
            "fcount": np.asarray(
                [s % self._seq_mod for s in shipped_now], np.int32
            ),
            "capacity": self._capacity,
            "frame_capacity": Cf_global,
        }
        for k, v in stage.items():
            out[f"stage_{k}"] = v
        return out

    def apply_delta_state_dict(self, delta: dict) -> None:
        if "delta" not in delta:
            raise ValueError("not a delta snapshot (missing 'delta' key)")
        if int(delta["n_shards"]) != self._n_shards:
            raise ValueError(
                f"delta has {int(delta['n_shards'])} shards, configured "
                f"{self._n_shards}"
            )
        if (int(delta["capacity"]) != self._capacity
                or int(delta["frame_capacity"])
                != self._replay.frame_capacity):
            raise ValueError("delta ring layout != configured layout")
        with self._lock:
            ing_now, shipped_now = self._chain_now()
            prev = np.asarray(delta["chain_prev"]).reshape(-1)
            if (int(prev[0]) != ing_now
                    or tuple(int(x) for x in prev[1:]) != shipped_now):
                raise ValueError(
                    f"delta chain discontinuity: delta continues "
                    f"{tuple(int(x) for x in prev)}, replay is at "
                    f"{(ing_now, *shipped_now)}"
                )
        import jax.numpy as jnp

        r = self._replay
        if self._mesh is not None:
            place = lambda key, live: jax.device_put(  # noqa: E731
                np.asarray(delta[key]).reshape(live.shape), live.sharding
            )
        else:
            place = lambda key, live: jnp.asarray(  # noqa: E731
                np.asarray(delta[key]).reshape(live.shape)
            )
        ti = jnp.asarray(np.asarray(delta["txn_gidx"], np.int32))
        fi = jnp.asarray(np.asarray(delta["frame_gidx"], np.int32))
        self._replay = r.replace(
            rows=r.rows.at[fi].set(
                jnp.asarray(r.fmt.pack(np.asarray(delta["frame_rows"])))
            ),
            obs_ref=r.obs_ref.at[ti].set(
                jnp.asarray(np.asarray(delta["txn_obs_ref"], np.int32))
            ),
            next_ref=r.next_ref.at[ti].set(
                jnp.asarray(np.asarray(delta["txn_next_ref"], np.int32))
            ),
            action=r.action.at[ti].set(
                jnp.asarray(np.asarray(delta["txn_action"], np.int32))
            ),
            reward=r.reward.at[ti].set(
                jnp.asarray(np.asarray(delta["txn_reward"], np.float32))
            ),
            discount=r.discount.at[ti].set(
                jnp.asarray(np.asarray(delta["txn_discount"], np.float32))
            ),
            mass=place("mass", r.mass),
            cursor=place("cursor", r.cursor),
            count=place("count", r.count),
            fcount=place("fcount", r.fcount),
        )
        with self._lock:
            self._stager.load_state_dict({
                k[len("stage_"):]: np.asarray(v) for k, v in delta.items()
                if k.startswith("stage_")
            })
            self._size = int(np.sum(np.asarray(delta["count"])))
            self._ckpt = self._chain_now()

    def load_state_dict(self, state: dict) -> None:
        if "dedup" not in state:
            raise ValueError(
                "snapshot is not a dedup-ring snapshot — replay layouts "
                "(replay.dedup) must match across save/restore"
            )
        fmt = self._replay.fmt
        want = (self._replay.frame_capacity, *fmt.obs_shape)
        got = tuple(state["frames"].shape)
        if want != got:
            raise ValueError(
                f"replay snapshot frame ring {got} != configured {want}"
            )
        if tuple(np.shape(state["cursor"])) != tuple(self._replay.cursor.shape):
            raise ValueError(
                "snapshot shard layout != configured data_parallel extent"
            )
        if self._mesh is not None:
            place = lambda key, live: jax.device_put(  # noqa: E731
                np.asarray(state[key]), live.sharding
            )
        else:
            place = lambda key, live: jnp.asarray(state[key])  # noqa: E731
        # A snapshot holds the logical [rows, *obs_shape] ring; the rows
        # are packed on the host, so the device never holds both.
        state = dict(
            state, rows=fmt.pack(np.asarray(state["frames"], fmt.dtype)))
        self._replay = self._replay.replace(
            rows=place("rows", self._replay.rows),
            obs_ref=place("obs_ref", self._replay.obs_ref),
            next_ref=place("next_ref", self._replay.next_ref),
            action=place("action", self._replay.action),
            reward=place("reward", self._replay.reward),
            discount=place("discount", self._replay.discount),
            mass=place("mass", self._replay.mass),
            cursor=place("cursor", self._replay.cursor),
            count=place("count", self._replay.count),
            fcount=place("fcount", self._replay.fcount),
        )
        self._size = int(np.sum(state["count"]))
        with self._lock:
            self._stager.load_state_dict({
                k[len("stage_"):]: v for k, v in state.items()
                if k.startswith("stage_")
            })
            # Full load invalidates dirty-span tracking: next incremental
            # save is a base unless deltas follow (checkpoint_inc applies
            # them via apply_delta_state_dict, which re-marks).
            self._ckpt = None

    def train(self, beta: float):
        self._rng, sub = jax.random.split(self._rng)
        self._state, self._replay, metrics = self._fused(
            self._state, self._replay, beta, sub
        )
        return metrics
