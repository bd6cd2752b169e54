"""Traffic driver ``learner_feed``: the fused learner fed alone.

Every fused call is preceded by one ingest of ``ingest_block`` rows from
device-resident chunks made from the seed (folded into the call where the
layout's builder folds it, dispatched before it where the runtime does).
The ring is full from the start, filled on the device from the seed.  Up to
``in_flight`` calls are dispatched before the oldest is forced by a host
read of its loss; the time of that read is the call's completion.

``check_shots`` runs the same dispatches on a small ring from weights made
by ``correctness`` and hands over what the comparison reads.
"""

from __future__ import annotations

import collections
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

import correctness
import program
import timing
from reference.prioritized_ring import DATA_FIELDS
from spans import Spans


# ---------------------------------------------------------------- the ring

def _random_rows(key, rows: int, row_shape, block: int = 2048):
    """uint8 [rows, *row_shape]: random bytes, each row dimmed by a level of
    its own (so two rows give different Q values), made block by block."""
    block = min(block, rows)

    def body(i, buf):
        kb, kl = jax.random.split(jax.random.fold_in(key, i))
        level = jax.random.randint(kl, (block,) + (1,) * len(row_shape), 32, 257, jnp.uint16)
        bits = jax.random.bits(kb, (block, *row_shape), jnp.uint8)
        bits = ((bits.astype(jnp.uint16) * level) >> 8).astype(jnp.uint8)
        return jax.lax.dynamic_update_slice_in_dim(
            buf, bits, jnp.minimum(i * block, rows - block), 0)

    return jax.lax.fori_loop(0, -(-rows // block), body,
                             jnp.zeros((rows, *row_shape), jnp.uint8))


def _meta(key, rows: int, cfg: dict, priority=(0.5, 1.5)):
    ka, kr, kd, kp = jax.random.split(key, 4)
    return dict(
        action=jax.random.randint(ka, (rows,), 0, cfg["num_actions"], jnp.int32),
        reward=jax.random.normal(kr, (rows,), jnp.float32),
        discount=jnp.where(jax.random.uniform(kd, (rows,)) < 0.02, 0.0,
                           cfg["gamma"] ** cfg["n_step"]).astype(jnp.float32),
        priority=jax.random.uniform(kp, (rows,), jnp.float32, *priority),
    )


def _full_double_store(key, cfg: dict, capacity: int):
    from ape_x_dqn_tpu.replay.device import DeviceReplayState

    ko, kn, km = jax.random.split(key, 3)
    m = _meta(km, capacity, cfg)
    return DeviceReplayState(
        obs=_random_rows(ko, capacity, cfg["obs_shape"]),
        next_obs=_random_rows(kn, capacity, cfg["obs_shape"]),
        action=m["action"], reward=m["reward"], discount=m["discount"],
        mass=jnp.power(m["priority"], cfg["priority_exponent"]),
        cursor=jnp.zeros((), jnp.int32), count=jnp.asarray(capacity, jnp.int32),
    )


def _full_dedup(key, cfg: dict, capacity: int, frame_capacity: int):
    """A full dedup ring (one shard's, under a mesh): frame seqs
    0..Cf-1 written, transition i references observation i*Cf/C and the one
    n_step later, every row alive."""
    from ape_x_dqn_tpu.replay.device_dedup import DedupDeviceReplayState

    kf, km = jax.random.split(key)
    m = _meta(km, capacity, cfg)
    i = jnp.arange(capacity, dtype=jnp.int32)
    extra = frame_capacity - capacity
    obs_ref = i + (i // (capacity // extra) if extra > 0 else 0)
    return DedupDeviceReplayState(
        frames=_random_rows(kf, frame_capacity, cfg["obs_shape"]),
        obs_ref=obs_ref,
        next_ref=jnp.minimum(obs_ref + cfg["n_step"], frame_capacity - 1),
        action=m["action"], reward=m["reward"], discount=m["discount"],
        mass=jnp.power(m["priority"], cfg["priority_exponent"]),
        cursor=jnp.zeros((), jnp.int32), count=jnp.asarray(capacity, jnp.int32),
        fcount=jnp.asarray(frame_capacity, jnp.int32),
    )


def _pack(st):
    return st.replace(cursor=st.cursor[None], count=st.count[None], fcount=st.fcount[None])


class Feed:
    """The ring, the resident chunks and the per-call dispatches of one
    configuration.  ``call(i)`` dispatches call i's ingest and fused program
    and returns its metrics without waiting."""

    def __init__(self, cfg: dict, traffic: dict, key, spans):
        self.cfg, self.spans = cfg, spans
        self.mesh = program.make_mesh(cfg)
        self.n = int(cfg.get("data_parallel", 1))
        self.net, self.opt, self.step_fn = program.build_learner(cfg)
        self.fused = program.build_fused(cfg, self.step_fn, self.mesh)
        self.fused_name = program.program_name(self.fused)
        k_state, k_ring, k_chunks, k_run = jax.random.split(key, 4)
        self.state = program.init_state(cfg, self.net, self.opt, k_state, self.mesh)
        self.key = k_run
        self.beta = float(traffic["beta"])
        self.resident = int(traffic["resident_chunks"])
        self.chunk_priority = tuple(traffic["chunk_priority"])
        self.rows = int(cfg["ingest_block"])
        if self.rows % self.n or cfg["replay_capacity"] % self.n:
            raise ValueError("ingest_block and replay_capacity must divide by data_parallel")
        self._compiled = None
        getattr(self, "_setup_" + cfg["replay_layout"])(k_ring, k_chunks)

    # -- double-store ring, ingest folded into the fused call (bench.py) --
    def _setup_double_store(self, k_ring, k_chunks):
        if self.mesh is not None:
            raise ValueError("double_store under a mesh is not driven here")
        from ape_x_dqn_tpu.types import NStepTransition

        cfg = self.cfg
        self.replay = jax.jit(
            lambda k: _full_double_store(k, cfg, cfg["replay_capacity"]))(k_ring)

        def chunk(k):
            ko, kn, km = jax.random.split(k, 3)
            m = _meta(km, self.rows, cfg, self.chunk_priority)
            return NStepTransition(
                obs=_random_rows(ko, self.rows, cfg["obs_shape"]),
                action=m["action"], reward=m["reward"], discount=m["discount"],
                next_obs=_random_rows(kn, self.rows, cfg["obs_shape"]),
            ), m["priority"]

        make = jax.jit(chunk)
        self.chunks = [make(k) for k in jax.random.split(k_chunks, self.resident)]
        self._dispatch = self._call_double_store

    def _call_double_store(self, i, sub):
        with self.spans.span("ingest"):
            chunk, prio = self.chunks[i % self.resident]
        with self.spans.span("dispatch"):
            self.state, self.replay, metrics = self._run_fused(
                self.state, self.replay, chunk, prio, self.beta, sub)
        self.last_chunk = dict(
            obs=chunk.obs, next_obs=chunk.next_obs, action=chunk.action,
            reward=chunk.reward, discount=chunk.discount, priority=prio)
        return metrics

    # -- dedup ring, one chip or sharded: frames, then transitions, then the scan --
    def _setup_dedup(self, k_ring, k_chunks):
        cfg, n, mesh = self.cfg, self.n, self.mesh
        c_local = cfg["replay_capacity"] // n
        cf_local = int(round(c_local * cfg["frame_ratio"]))
        self.frames_per_call = int(round(self.rows // n * cfg["frame_ratio"]))
        if self.frames_per_call < self.rows // n + cfg["n_step"]:
            raise ValueError("frame_ratio leaves no room for the n-step references")
        self.fcount = cf_local  # frame seqs 0..Cf-1 are written by the fill
        self.seq_mod = ((1 << 30) // cf_local) * cf_local
        pe = cfg["priority_exponent"]

        def chunk_local(k):
            kf, km = jax.random.split(k)
            m = _meta(km, self.rows // n, cfg, self.chunk_priority)
            return (_random_rows(kf, self.frames_per_call, cfg["obs_shape"]),
                    m["action"], m["reward"], m["discount"], m["priority"])

        if mesh is None:
            from ape_x_dqn_tpu.replay.device_dedup import (
                dedup_device_add_frames, dedup_device_add_transitions,
            )

            self.replay = jax.jit(lambda k: _full_dedup(k, cfg, c_local, cf_local))(k_ring)
            make = jax.jit(chunk_local)
            self._add_frames = jax.jit(dedup_device_add_frames, donate_argnums=(0,))
            self._add_txns = jax.jit(
                lambda st, o, nx, a, r, d, p: dedup_device_add_transitions(
                    st, o, nx, a, r, d, p, pe),
                donate_argnums=(0,))
            self._place_refs = lambda a: jnp.asarray(a[0])
        else:
            from jax import shard_map
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ape_x_dqn_tpu.replay.device_dedup_dp import (
                build_sharded_dedup_add_frames, build_sharded_dedup_add_transitions,
                dedup_replay_specs,
            )

            def shard_key(k):
                return jax.random.fold_in(k, jax.lax.axis_index(program.AXIS))

            self.replay = jax.jit(shard_map(
                lambda k: _pack(_full_dedup(shard_key(k), cfg, c_local, cf_local)),
                mesh=mesh, in_specs=P(), out_specs=dedup_replay_specs(),
                check_vma=False))(k_ring)
            make = jax.jit(shard_map(
                lambda k: jax.tree_util.tree_map(
                    lambda x: x[None], chunk_local(shard_key(k))),
                mesh=mesh, in_specs=P(), out_specs=P(program.AXIS), check_vma=False))
            self._add_frames = build_sharded_dedup_add_frames(mesh)
            self._add_txns = build_sharded_dedup_add_transitions(mesh, pe)
            row = NamedSharding(mesh, P(program.AXIS))
            self._place_refs = lambda a: jax.device_put(a, row)
        self.chunks = [make(k) for k in jax.random.split(k_chunks, self.resident)]
        self._dispatch = self._call_dedup

    def _call_dedup(self, i, sub):
        with self.spans.span("ingest"):
            frames, action, reward, discount, prio = self.chunks[i % self.resident]
            # The frames of this call take seqs fcount..fcount+U-1 on every
            # shard; row j references the j-th of them and the one n_step on.
            base = self.fcount + np.arange(self.rows // self.n, dtype=np.int64)
            obs_ref = np.tile((base % self.seq_mod).astype(np.int32), (self.n, 1))
            next_ref = np.tile(((base + self.cfg["n_step"]) % self.seq_mod).astype(np.int32),
                               (self.n, 1))
            self.fcount = (self.fcount + self.frames_per_call) % self.seq_mod
            self.replay = self._add_frames(self.replay, frames)
            self.replay = self._add_txns(
                self.replay, self._place_refs(obs_ref), self._place_refs(next_ref),
                action, reward, discount, prio)
        with self.spans.span("dispatch"):
            self.state, self.replay, metrics = self._run_fused(
                self.state, self.replay, self.beta, sub)
        if self.mesh is None:
            obs_ref, next_ref = obs_ref[0], next_ref[0]
        self.last_chunk = dict(
            frames=frames, obs_ref=obs_ref, next_ref=next_ref, action=action,
            reward=reward, discount=discount, priority=prio)
        return metrics

    def _run_fused(self, *args):
        """The fused program, compiled ahead on its first arguments so that
        the compiler's count of its temporaries can be read."""
        if self._compiled is None:
            self._compiled = self.fused.lower(*args).compile()
        return self._compiled(*args)

    def temp_bytes(self) -> int:
        """Bytes of temporaries the fused program holds on a chip while it
        runs, by the compiler's count."""
        return int(self._compiled.memory_analysis().temp_size_in_bytes)

    def call(self, i: int):
        self.key, sub = jax.random.split(self.key)
        return self._dispatch(i, sub)

    # -- host copies, per shard, for the comparison --
    def _per_shard(self, tree: dict, stacked: bool = False) -> list:
        """[{field: numpy array of shard d}]: arrays split along their first
        axis (or, ``stacked``, indexed by it), the ring's counters one to a
        shard."""
        out = [dict() for _ in range(self.n)]
        for f, x in tree.items():
            a = np.asarray(x)
            if f in ("cursor", "count", "fcount"):
                a = a.reshape(self.n)
            elif not stacked:
                a = a.reshape(self.n, -1, *a.shape[1:])
            for d in range(self.n):
                out[d][f] = a[d]
        return out

    def host_ring(self) -> list:
        fields = DATA_FIELDS[self.cfg["replay_layout"]] + ("mass", "cursor", "count")
        if self.cfg["replay_layout"] == "dedup":
            fields += ("fcount",)
        return self._per_shard({f: getattr(self.replay, f) for f in fields})

    def host_chunk(self) -> list:
        return self._per_shard(self.last_chunk, stacked=self.mesh is not None)


# ------------------------------------------------------------- the window

def _force(metrics, spans) -> np.ndarray:
    with spans.span("force"):
        return np.asarray(metrics.loss)


def _pump(feed: Feed, spans, first_call: int, in_flight: int, stop) -> dict:
    """Dispatch calls, forcing the oldest once ``in_flight`` are out, until
    ``stop(completions)``; then drain.  Completion = the host read of a
    call's loss returned."""
    pending = collections.deque()
    completions, losses, failed, i = [], [], 0, first_call
    while True:
        try:
            pending.append(feed.call(i))
        except Exception:  # noqa: BLE001 - counted; the states were donated, so the pump ends
            traceback.print_exc()
            failed += 1
        i += 1
        if failed:
            break
        if len(pending) >= in_flight:
            losses.append(_force(pending.popleft(), spans))
            completions.append(time.perf_counter())
            if stop(completions):
                break
    while pending:
        losses.append(_force(pending.popleft(), spans))
    failed += sum(1 for x in losses if not np.all(np.isfinite(x)))
    return dict(completions=completions, next_call=i, attempted=i - first_call,
                failed=failed,
                last_loss=float(losses[-1][-1]) if losses else float("nan"))


def run(ctx) -> dict:
    """``ctx``: cell (config, traffic), seed, seconds, trace, spans, out_dir,
    compile_events.  Returns what run.py and the per-layer readers read."""
    cfg, traffic, spans = ctx.cell.config, ctx.cell.traffic, ctx.spans
    feed = Feed(cfg, traffic, program.seed_key(ctx.seed), spans)
    k_steps, batch = cfg["steps_per_call"], cfg["batch_size"]
    in_flight = int(traffic["in_flight"])

    warm = _pump(feed, spans, 0, 1, lambda c: len(c) >= int(traffic["warmup_calls"]))
    compiles_before = ctx.compile_events()
    start = warm["completions"][-1]
    ctx.mark_setup_done(start)
    win = _pump(feed, spans, warm["next_call"], in_flight,
                lambda c: timing.window_done(start, c, ctx.seconds))
    compiles_in_window = ctx.compile_events() - compiles_before
    interval = timing.call_boundary_interval(start, win["completions"], ctx.seconds)
    calls_done = win["next_call"]

    obs = dict(
        end_to_end={"learn_samples_per_s": interval.rate(k_steps * batch)},
        attempted=warm["attempted"] + win["attempted"],
        failed=warm["failed"] + win["failed"],
        window=(start, start + interval.elapsed_s),
        counters=dict(calls_in_window=interval.calls, steps_per_call=k_steps,
                      batch_size=batch, compiles_in_window=compiles_in_window,
                      last_loss=win["last_loss"]),
        fused_program=feed.fused_name,
    )

    if ctx.trace:
        mean_call = interval.elapsed_s / interval.calls
        n_trace = max(in_flight + 1, int(np.ceil(float(traffic["trace_seconds"]) / mean_call)))
        with ctx.profiler():
            tr = _pump(feed, spans, calls_done, in_flight, lambda c: len(c) >= n_trace)
        calls_done = tr["next_call"]
        obs["attempted"] += tr["attempted"]
        obs["failed"] += tr["failed"]
        obs["counters"]["traced_calls"] = tr["attempted"]

    step = int(jax.device_get(feed.state.step))
    obs["exact_checks"] = [
        ("step counter", step, calls_done * k_steps),
        ("compilations inside the window", compiles_in_window, 0),
        ("calls that raised or lost a finite loss", obs["failed"], 0),
    ]
    obs["program_temp_bytes"] = feed.temp_bytes()
    seed = ctx.seed
    obs["check"] = lambda: correctness.program_numbers(
        cfg, float(traffic["beta"]), *check_shots(cfg, traffic, seed))[:2]
    return obs


def check_shots(cfg: dict, traffic: dict, seed: int) -> tuple:
    """(inputs, shots) for ``correctness``: the program's fused learner at the
    configuration's widths and batch, on a ring of ``check.ring_rows_per_chip``
    rows with one step per call, started from weights made from the seed and
    dispatched as the window dispatches it.  ``shots`` holds host copies of
    the ring before the first and after every call, the chunks ingested, the
    priorities returned and the final parameters."""
    spec, n = traffic["check"], int(cfg.get("data_parallel", 1))
    small = dict(cfg, steps_per_call=1, replay_capacity=spec["ring_rows_per_chip"] * n,
                 ingest_block=spec["ingest_rows_per_chip"] * n)
    k_inputs, k_feed = jax.random.split(
        jax.random.fold_in(program.seed_key(seed), 0xC0FFEE))
    inputs = correctness.make_inputs(k_inputs, small)
    feed = Feed(small, dict(traffic, chunk_priority=spec["chunk_priority"]), k_feed, Spans())
    feed.state = program.state_from_inputs(small, feed.opt, inputs, feed.mesh)
    shots = dict(rings=[feed.host_ring()], chunks=[], priorities=[])
    for i in range(int(spec["calls"])):
        metrics = feed.call(i)
        shots["chunks"].append(feed.host_chunk())
        shots["rings"].append(feed.host_ring())
        shots["priorities"].append(np.asarray(metrics.priorities).reshape(-1))
    shots["params"] = feed.state.params
    return inputs, shots
