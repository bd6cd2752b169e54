"""Process-parallel actors: N worker processes feeding one learner.

The reference's actors are ``mp.Process`` instances (reference
actor.py:96-97, main.py:50-54) wired through a manager dict (params) and a
manager queue (experience).  The thread-based fleet (runtime/async_pipeline)
covers fake/vector envs, but real emulators hold the GIL — SURVEY §7 hard
part #3 — so the scale configs need actors in separate *processes*.  This
module is that mode, on the TPU-native transport stack:

  * **Param broadcast** — a single-writer shared-memory seqlock ring
    (``SharedParamBuffer``) holding one serialized snapshot
    (utils/serialization wire format).  The learner writes at its capped
    publish rate; workers poll versions and deserialize only on change.
    Versus the reference's manager dict: no server process, no pickle of
    live objects, readers never block the writer.  The same snapshot bytes
    are what a DCN fetch would ship between hosts — the store is the seam
    (runtime/param_store.py).
  * **Experience transport** — pluggable behind ``runtime/transport.py``
    (``actor.transport``).  Default: one SIGKILL-safe single-producer/
    single-consumer shared-memory ring per worker incarnation
    (``runtime/shm_ring.ShmRing``): workers gather chunks into the ring in
    the ``utils/serialization`` APXT wire format (numpy frame bytes written
    once, no pickle), the learner drains every ring in one batched sweep
    per poll and hands whole chunks to replay ingest as zero-copy views.
    A worker killed mid-record leaves a detectably torn tail instead of a
    held lock — the salvage-and-respawn discipline ``mp.Queue`` could only
    approximate by abandoning a whole queue.  The ``tcp`` backend
    (``runtime/net.py``) carries the identical CRC-framed records over a
    socket per worker — loopback or cross-host — with params fanned out
    on the same connection as delta-or-full framed messages; the pool's
    poll/salvage/stats paths are identical either way.  ``mp.Queue``
    remains as a low-volume CONTROL channel (done/error/episode stats
    only).
  * **Worker processes** are CPU-only JAX (``JAX_PLATFORMS=cpu`` assigned
    in the child, and checked, before any backend initialises): exactly
    one process — the learner — owns the TPU.  Each worker runs an
    ``ActorFleet`` over its slice of the global actor set, with the
    ε-ladder indexed globally (pool.py ``epsilon_index_offset``) so
    exploration diversity matches the single-process layout.

This module stays import-light (stdlib + numpy only at module scope): the
spawn-context child imports it before the worker target runs, and the env
var gating jax's backend must be set before any jax import.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import struct
import threading
import time
from multiprocessing import shared_memory
from typing import Any, List, Optional

import numpy as np

from ape_x_dqn_tpu.obs.recorder import FlightRecorder, write_postmortem
from ape_x_dqn_tpu.obs.shm_stats import WORKER_SLOTS, WorkerStatsBlock
from ape_x_dqn_tpu.runtime.shm_ring import (
    DXP,
    XP,
    ShmRing,
    decode_chunk,
    encode_chunk_parts,
)
from ape_x_dqn_tpu.runtime.transport import (
    NetParamSource,
    NetParamStore,
    connect_channel,
    make_transport,
)

_HEADER = struct.Struct("<qqI")  # (seqlock version, payload length, crc32)


class SharedParamBuffer:
    """Single-writer seqlock over one shared-memory snapshot slot.

    Write protocol: bump version to odd, copy payload, commit crc32 +
    even version.  Read protocol: spin until an even version reads
    identically before and after the payload copy AND the copied payload's
    crc32 matches the committed header.  The single writer (the learner)
    never blocks; readers retry only during the microseconds a write is in
    flight.

    Memory-ordering note: the version-recheck alone is only sound on
    TSO-ordered CPUs (x86) — Python buffer stores carry no fences, so a
    weakly-ordered host (ARM) could make payload stores visible *after* the
    even-version store and admit a torn read.  The crc32 closes that hole:
    a reader accepts a payload only if its checksum matches the committed
    header, so any interleaving that mixes bytes of two snapshots is
    detected and retried regardless of store visibility order.
    """

    def __init__(self, capacity: int, name: Optional[str] = None,
                 create: bool = True):
        self.capacity = int(capacity)
        size = _HEADER.size + self.capacity
        if create:
            from ape_x_dqn_tpu.runtime.shm_ring import create_shared_memory

            self._shm = create_shared_memory("params", size)
            _HEADER.pack_into(self._shm.buf, 0, 0, 0, 0)
        else:
            self._shm = shared_memory.SharedMemory(name=name)
        self._owner = create

    @property
    def name(self) -> str:
        return self._shm.name

    @property
    def version(self) -> int:
        return _HEADER.unpack_from(self._shm.buf, 0)[0] // 2

    def write(self, payload: bytes) -> int:
        import zlib

        if len(payload) > self.capacity:
            raise ValueError(
                f"snapshot of {len(payload)} bytes exceeds shared buffer "
                f"capacity {self.capacity}"
            )
        v, _, _ = _HEADER.unpack_from(self._shm.buf, 0)
        _HEADER.pack_into(self._shm.buf, 0, v + 1, len(payload), 0)  # odd: in flight
        self._shm.buf[_HEADER.size:_HEADER.size + len(payload)] = payload
        _HEADER.pack_into(                                     # even: committed
            self._shm.buf, 0, v + 2, len(payload), zlib.crc32(payload)
        )
        return (v + 2) // 2

    def read(self, have_version: int = -1,
             timeout: float = 1.0) -> Optional[tuple]:
        """Return (payload bytes, version) if newer than have_version.

        Bounded: if a write stays in flight past ``timeout`` (e.g. the
        writer died mid-write, leaving the version odd), returns None so
        callers keep polling their own stop conditions instead of hanging.
        """
        import zlib

        deadline = time.monotonic() + timeout
        while True:
            v1, length, _ = _HEADER.unpack_from(self._shm.buf, 0)
            if v1 % 2 == 0:
                if v1 // 2 <= have_version or length == 0:
                    return None
                payload = bytes(self._shm.buf[_HEADER.size:_HEADER.size + length])
                v2, _, crc = _HEADER.unpack_from(self._shm.buf, 0)
                if v1 == v2 and zlib.crc32(payload) == crc:
                    return payload, v1 // 2
                # torn read: a write landed mid-copy, or (weakly-ordered
                # hosts) payload stores weren't yet visible — retry
            if time.monotonic() > deadline:
                return None
            time.sleep(0.0005)

    def close(self):
        self._shm.close()
        if self._owner:
            try:
                self._shm.unlink()
            except FileNotFoundError:
                pass


class SharedMemoryParamStore:
    """ParamStore facade whose publishes land in the shared seqlock buffer.

    Exposes the same surface the async pipeline and thread fleets use
    (``publish`` / ``get`` / ``get_blocking`` / ``version``) so one runtime
    code path drives both thread and process actor modes; the in-process
    ``get`` additionally serves any learner-side readers without a
    deserialize round trip.
    """

    def __init__(self, buffer: SharedParamBuffer):
        import jax

        self._jax = jax
        self._buf = buffer
        self._lock = threading.Lock()
        self._params = None  # host copy for in-process readers
        # This store is the buffer's single writer, so a local counter IS
        # the buffer version — and it survives the buffer being closed at
        # shutdown (metrics/asserts read it after stop()).
        self._version = 0

    @property
    def version(self) -> int:
        return self._version

    def publish(self, params: Any) -> int:
        from ape_x_dqn_tpu.utils.serialization import tree_to_bytes

        host = self._jax.device_get(params)
        payload = tree_to_bytes(host)
        with self._lock:
            self._params = host
            self._version = self._buf.write(payload)
            return self._version

    def get(self, have_version: int = -1):
        with self._lock:
            if self._params is None or self._version <= have_version:
                return None
            return self._params, self._version

    def get_blocking(self, timeout: float = 30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            got = self.get(-1)
            if got is not None:
                return got
            time.sleep(0.01)
        raise TimeoutError("no parameters published within timeout")


class SharedBufferParamSource:
    """Worker-side ``ParamSource``: poll the seqlock buffer, deserialize
    into the worker's own param template on version change (pool.py's
    ``sync_params`` contract: ``get(have_version) -> (params, version)``)."""

    def __init__(self, buffer: SharedParamBuffer, template: Any):
        self._buf = buffer
        self._template = template

    def get(self, have_version: int = -1):
        got = self._buf.read(have_version)
        if got is None:
            return None
        payload, version = got
        from ape_x_dqn_tpu.utils.serialization import restore_like

        return restore_like(self._template, payload), version


def worker_slice(worker_id: int, num_actors: int, num_workers: int) -> tuple:
    """[lo, hi) of the global actor set owned by ``worker_id`` — the ONE
    partition rule, used by both the worker (fleet construction) and the
    pool (restart-budget accounting)."""
    lo = worker_id * num_actors // num_workers
    hi = (worker_id + 1) * num_actors // num_workers
    return lo, hi


def _cfg_from_dict(cfg_dict: dict):
    from ape_x_dqn_tpu.config import (
        ActorConfig, ApexConfig, ChaosConfig, EnvConfig, LearnerConfig,
        ObsConfig, ReplayConfig,
    )

    return ApexConfig(
        env=EnvConfig(**cfg_dict["env"]),
        actor=ActorConfig(**cfg_dict["actor"]),
        learner=LearnerConfig(**cfg_dict["learner"]),
        replay=ReplayConfig(**cfg_dict["replay"]),
        obs=ObsConfig(**cfg_dict.get("obs", {})),
        chaos=ChaosConfig(**cfg_dict.get("chaos", {})),
        network=cfg_dict["network"],
        torso=cfg_dict.get("torso", {}),
        seed=cfg_dict["seed"],
    )


def network_and_template(cfg):
    """(env_kwargs, network, template_params) without touching replay or
    checkpoints — what a worker (or the pool's buffer sizing) needs.  Param
    *structure* matches the learner's because ``build_components`` inits
    from the same network definition; values are irrelevant to a template."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.envs import make_env
    from ape_x_dqn_tpu.config import network_kwargs
    from ape_x_dqn_tpu.models.dueling import build_network

    env_kwargs = dict(
        frame_skip=cfg.env.frame_skip,
        frame_stack=cfg.env.frame_stack,
        episodic_life=cfg.env.episodic_life,
        clip_rewards=cfg.env.clip_rewards,
    )
    probe = make_env(cfg.env.name, seed=cfg.seed, **env_kwargs)
    net_kwargs = network_kwargs(cfg)
    if cfg.learner.param_dtype is not None:
        net_kwargs["param_dtype"] = {
            "bfloat16": jnp.bfloat16, "float32": jnp.float32,
        }[cfg.learner.param_dtype]
    network = build_network(cfg.network, probe.num_actions, **net_kwargs)
    params = network.init(
        jax.random.PRNGKey(cfg.seed),
        jnp.zeros((1, *probe.observation_shape), jnp.uint8),
    )
    return env_kwargs, network, params


def _worker_main(worker_id: int, cfg_dict: dict, num_workers: int,
                 param_spec: dict, xp_spec: dict, ctl_queue, stop_evt,
                 steps_budget: int, quantum: int, attempt: int = 0,
                 seed_base: int = 0, nice: int = 0,
                 stats_name: Optional[str] = None, retire_evt=None):
    """Worker process entry: CPU-only jax, one ActorFleet slice, gather
    chunks into this incarnation's transport channel (shm ring or TCP
    connection — ``xp_spec`` names the backend); episode stats /
    completion / errors ride the low-volume control queue.  Params arrive
    per ``param_spec``: the shared seqlock buffer (shm) or delta/full
    frames on the experience connection (tcp).  Metrics ride the
    incarnation's shm stats block (obs/shm_stats): slots +
    flight-recorder events the parent can read even after a SIGKILL."""
    if nice:
        # QoS: on hosts where workers share cores with the learner, a
        # positive niceness keeps the learner's dispatch thread scheduled
        # first (actor.worker_nice).
        try:
            os.nice(int(nice))
        except OSError:
            pass
    os.environ["JAX_PLATFORMS"] = "cpu"  # before the first jax import
    # Don't inherit the test harness's virtual-device forcing: 8 fake CPU
    # devices per worker only slow the fleet's single-device jit down.
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in flags.split()
        if "force_host_platform_device_count" not in f
    )
    # The spawn child re-imports the parent's ``__main__`` before this
    # function runs; if that module imports jax at module scope, jax has
    # already read JAX_PLATFORMS and the assignment above came too late.
    # No backend has initialised yet, so the config update still wins —
    # and a worker that would land anywhere but the CPU dies here rather
    # than contend with the learner for its chip.
    import jax as _jax

    _jax.config.update("jax_platforms", "cpu")
    backend = _jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"actor worker {worker_id} must run on the CPU, got backend "
            f"{backend!r}: one process owns the chip"
        )
    from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    buf = None
    ring = None
    sblock = None
    selector = None
    try:
        from ape_x_dqn_tpu.actors import ActorFleet
        from ape_x_dqn_tpu.envs import make_env
        from ape_x_dqn_tpu.runtime.components import (
            dedup_groups as _dedup_groups,
        )
        from ape_x_dqn_tpu.utils.memory import trim_malloc

        cfg = _cfg_from_dict(cfg_dict)
        N = cfg.actor.num_actors
        lo, hi = worker_slice(worker_id, N, num_workers)
        if hi == lo:
            ctl_queue.put(("done", worker_id, 0))
            return
        env_kwargs, network, template = network_and_template(cfg)
        env_fns = [
            (lambda i=i: make_env(
                cfg.env.name, seed=cfg.seed + 1000 + i, **env_kwargs
            ))
            for i in range(lo, hi)
        ]
        if cfg.chaos.enabled and cfg.chaos.env_latency_ms > 0:
            # Slow-env chaos (obs/chaos.SlowEnv): seeded per actor so the
            # injected latency stream reproduces with the run.
            from ape_x_dqn_tpu.obs.chaos import SlowEnv

            lat_s = cfg.chaos.env_latency_ms / 1e3
            env_fns = [
                (lambda fn=fn, i=i: SlowEnv(
                    fn(), lat_s, seed=cfg.chaos.seed + 71 * i
                ))
                for i, fn in enumerate(env_fns)
            ]
        fleet = ActorFleet(
            env_fns,
            network,
            n_step=cfg.actor.num_steps,
            gamma=cfg.actor.gamma,
            epsilon=cfg.actor.epsilon,
            epsilon_alpha=cfg.actor.alpha,
            flush_every=cfg.actor.flush_every,
            sync_every=cfg.actor.sync_every,
            # Respawned incarnations explore a fresh stream (thread mode's
            # seed_offset twin); seed_base separates hosts under SPMD.
            seed=cfg.seed + 9000 + worker_id + 100_000 * attempt + seed_base,
            epsilon_index_offset=lo,
            epsilon_total=N,
            emission=cfg.actor.emission,
            emit_dedup=cfg.replay.dedup,
            emit_dedup_groups=_dedup_groups(cfg),
        )
        ring = connect_channel(xp_spec)
        central = cfg.actor.inference == "central"
        if param_spec["kind"] == "shm":
            buf = SharedParamBuffer(param_spec["capacity"],
                                    name=param_spec["name"], create=False)
            source = SharedBufferParamSource(buf, template)
        elif param_spec["kind"] == "net":
            # tcp: params ride the experience connection in reverse.
            source = NetParamSource(ring, template)
        else:
            # "none": central-paramless — the learner fans out NO params
            # to this worker; action selection is the serving tier's.
            source = None
        # Observability: the incarnation's shm stats block (parent-created;
        # this worker is the single writer) + a flight recorder mirrored
        # into its event ring.  Metrics must never kill a worker — any
        # failure here degrades to "no stats", not an error.
        if stats_name:
            try:
                sblock = WorkerStatsBlock(name=stats_name, create=False)
            except Exception:  # noqa: BLE001 — degrade, don't die
                sblock = None
        recorder = FlightRecorder(
            name=f"worker{worker_id}", depth=cfg.obs.recorder_depth,
            shm_sink=sblock,
        )
        eps = np.asarray(fleet._epsilons)
        if sblock is not None:
            sblock.update(
                eps_mean=float(eps.mean()), eps_min=float(eps.min()),
                eps_max=float(eps.max()),
            )
        recorder.record(
            "spawn", worker=worker_id, attempt=attempt, lo=lo, hi=hi,
            budget=steps_budget, platform=backend,
        )
        # Lineage trace sampling (obs/lineage): a sampled chunk carries a
        # random nonzero 63-bit id on the wire envelope.
        import random as _random

        trace_rng = _random.Random(
            (os.getpid() << 20) ^ (worker_id << 8) ^ attempt
        )
        trace_rate = float(cfg.obs.trace_sample_rate)
        chunks_sent = 0
        transitions_sent = 0
        episodes_total = 0
        collect_s = 0.0
        write_s = 0.0
        # Central inference (actor.inference=central): action selection
        # moves to the serving tier — build the pipelined client +
        # selector from the config's endpoint (the pool patches the
        # resolved auto endpoint into the cfg before spawn).  The worker
        # holds params only when the local fallback is configured.
        if central:
            from ape_x_dqn_tpu.serving.central import (
                CentralInferenceClient,
                CentralSelector,
                InferenceUnavailable,
            )

            client = CentralInferenceClient(
                cfg.actor.inference_host, cfg.actor.inference_port,
                wid=worker_id, attempt=attempt,
                token=cfg.actor.inference_token,
                codec=cfg.actor.inference_codec,
                dedup=cfg.actor.inference_dedup,
                inflight=cfg.actor.inference_inflight,
                seed=cfg.seed + worker_id,
                # Cross-tier tracing at the lineage sample rate: spans
                # mirror into this worker's recorder → shm event ring,
                # where the parent's trace sweep reads them.
                trace=trace_rate > 0,
                span_recorder=recorder,
            )
            fallback_fn = None
            if cfg.actor.inference_fallback == "local" and source is not None:
                def fallback_fn(obs, step, _fleet=fleet, _source=source):
                    # Cached-params local inference: opportunistic sync
                    # (keeps the last adopted snapshot on a quiet store),
                    # then the fleet's own jitted ε-greedy policy step —
                    # literally the local mode, per outage step.
                    _fleet.sync_params(_source)
                    if _fleet.params is None:
                        raise InferenceUnavailable(
                            "fallback configured but no param snapshot "
                            "adopted yet"
                        )
                    a, qv = _jax.device_get(_fleet._policy_step(
                        _fleet.params, obs, _fleet._epsilons, step
                    ))
                    return np.asarray(a), np.asarray(qv), \
                        _fleet.param_version
            selector = CentralSelector(
                client, np.asarray(fleet._epsilons),
                fleet.envs.num_actions,
                seed=cfg.seed + 77_000 + worker_id + 100_000 * attempt,
                timeout_s=cfg.actor.inference_timeout_s,
                trace_sample_rate=trace_rate,
                fallback=fallback_fn,
                should_stop=stop_evt.is_set,
            )
        if selector is None or cfg.actor.inference_fallback == "local":
            # Wait for the learner's first publication (the reference's
            # construct-learner-first ordering constraint, main.py:44).
            # Central-paramless workers skip it: their first action needs
            # a serving reply, not a snapshot.
            if source is not None:
                deadline = time.monotonic() + 60.0
                while not fleet.sync_params(source):
                    if selector is not None:
                        break  # fallback mode: don't gate on the store
                    if stop_evt.is_set() or time.monotonic() > deadline:
                        ctl_queue.put(("done", worker_id, 0))
                        return
                    time.sleep(0.01)
        # Autopilot retirement (pool.retire): a per-incarnation event that
        # ends the collect loop at the NEXT quantum boundary — the worker
        # flushes its committed chunks and exits through the clean "done"
        # path, exactly like an exhausted budget.  Never a SIGKILL.
        def _retiring() -> bool:
            return retire_evt is not None and retire_evt.is_set()

        while not stop_evt.is_set() and not _retiring() \
                and fleet.step_count < steps_budget:
            # Clamp the final quantum: the budget bounds TOTAL fleet steps
            # across incarnations, so the last collect must land exactly.
            t0 = time.monotonic()
            try:
                chunks, ep_stats = fleet.collect(
                    min(quantum, steps_budget - fleet.step_count),
                    param_source=source if selector is None else None,
                    selector=selector,
                )
            except Exception:
                if selector is not None and stop_evt.is_set():
                    break  # stop raced a central select: clean exit
                raise
            collect_s += time.monotonic() - t0
            t0 = time.monotonic()
            for c in chunks:
                trace_id = 0
                if trace_rate and trace_rng.random() < trace_rate:
                    trace_id = trace_rng.getrandbits(63) or 1
                if cfg.replay.dedup:
                    # DedupChunk arrays ship as APXT buffers; the int
                    # identity fields ride the record's metadata prefix.
                    d = c.transitions._asdict()
                    parts = encode_chunk_parts(
                        DXP, fleet.param_version, c.actor_steps,
                        {
                            "prio": np.asarray(c.priorities),
                            **{k: np.asarray(d[k])
                               for k in ("frames", "obs_ref", "next_ref",
                                         "action", "reward", "discount")},
                        },
                        source=d["source"], chunk_seq=d["chunk_seq"],
                        prev_frames=d["prev_frames"], trace_id=trace_id,
                    )
                else:
                    parts = encode_chunk_parts(
                        XP, fleet.param_version, c.actor_steps,
                        {
                            "prio": np.asarray(c.priorities),
                            **{f: np.asarray(getattr(c.transitions, f))
                               for f in ("obs", "action", "reward",
                                         "discount", "next_obs")},
                        },
                        trace_id=trace_id,
                    )
                # Backpressure: block on a full ring (bounded sleeps, the
                # learner's drain frees space) but abort promptly on stop —
                # a stopping learner no longer drains, and unlike mp.Queue
                # there is no shared lock a kill could strand.
                if not ring.write(parts, should_stop=stop_evt.is_set):
                    break
                chunks_sent += 1
                transitions_sent += len(c.priorities)
                if trace_id:
                    recorder.record(
                        "trace_chunk", trace_id=trace_id,
                        rows=len(c.priorities), v=fleet.param_version,
                    )
            # Quantum-boundary flush (tcp wire-efficiency layers): the
            # coalescing buffer must not hold records across a collect —
            # the max-wait bound is for bursts WITHIN a write loop, this
            # is the between-bursts bound.  shm rings have no flush.
            flush = getattr(ring, "flush", None)
            if flush is not None:
                flush(should_stop=stop_evt.is_set)
            write_s += time.monotonic() - t0
            if ep_stats:
                episodes_total += len(ep_stats)
                ctl_queue.put((
                    "episodes", worker_id,
                    [(s.actor_id + lo, s.episode_return, s.episode_length)
                     for s in ep_stats],
                ))
            if sblock is not None:
                # One batched slot write + heartbeat per quantum — the
                # cadence the parent's poll sweep reads.
                sblock.update(
                    env_steps=fleet.step_count, chunks=chunks_sent,
                    transitions=transitions_sent,
                    param_version=fleet.param_version,
                    episodes=episodes_total, collect_s=collect_s,
                    write_s=write_s,
                )
            if selector is not None:
                # Central-inference client accounting rides the control
                # queue at the quantum cadence (low volume: one dict) —
                # the pool folds it into the obs `inference` section.
                try:
                    ctl_queue.put_nowait((
                        "inference", worker_id,
                        selector.stats(include_hist=True),
                    ))
                except Exception:  # noqa: BLE001 — stats must not block
                    pass
            # Arena hygiene each quantum: the obs-batch allocation stream
            # otherwise grows worker RSS ~0.65 MB/s forever (utils/memory
            # docstring — measured in the round-5 flagship soak).
            trim_malloc()
        recorder.record("done", steps=fleet.step_count,
                        stopped=stop_evt.is_set(), retired=_retiring())
        if selector is not None:
            try:
                ctl_queue.put_nowait((
                    "inference", worker_id,
                    selector.stats(include_hist=True),
                ))
            except Exception:  # noqa: BLE001 — final stats best-effort
                pass
            selector.close()
        ctl_queue.put(("done", worker_id, fleet.step_count))
    except Exception as e:  # noqa: BLE001 — report, don't hang the join
        if sblock is not None:
            try:  # last words into the SIGKILL-proof event ring
                sblock.record_event({
                    "t": round(time.monotonic(), 4), "kind": "error",
                    "error": f"{type(e).__name__}: {e}",
                })
            except Exception:  # noqa: BLE001 — dying worker: the stats block may already be gone
                pass
        try:
            ctl_queue.put(("error", worker_id, f"{type(e).__name__}: {e}"))
        except Exception:  # noqa: BLE001 — last-breath error report; the queue may be closed
            pass
    finally:
        if selector is not None:
            # Close the serving connection on EVERY exit path (a socket
            # abandoned to process teardown can die mid-frame and count
            # torn server-side for nothing).  Idempotent with the
            # done-path close.
            try:
                selector.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if buf is not None:
            buf.close()
        if ring is not None:
            ring.close()
        if sblock is not None:
            sblock.close()


class ProcessActorPool:
    """Owner of N actor worker processes + the shared param buffer + one
    experience shm ring per worker incarnation.

    Lifecycle: ``start()`` → learner loop interleaves ``publish(params)``
    and ``poll()`` → ``stop()``.  ``poll`` drains every ring in one batched
    sweep (bounded by ``max_items`` and a byte budget) into (priorities,
    transitions) pairs, and the control queues into accounting.
    """

    def __init__(self, cfg, num_workers: int = 2,
                 shm_capacity: Optional[int] = None,
                 queue_size: int = 64, quantum: Optional[int] = None,
                 max_restarts: int = 3, seed_base: int = 0,
                 ring_bytes: Optional[int] = None,
                 drain_budget_bytes: Optional[int] = None,
                 postmortem_dir: Optional[str] = None):
        import jax

        from ape_x_dqn_tpu.config import to_dict
        from ape_x_dqn_tpu.types import NStepTransition
        from ape_x_dqn_tpu.utils.metrics import TransportStats

        self._NStepTransition = NStepTransition
        self.cfg = cfg
        self.num_workers = int(num_workers)
        # Remote-worker slots (actor.remote_workers; tools/host_join.py):
        # extra wids beyond the local fleet, carved from the SAME global
        # actor partition.  The pool pre-registers their channels and
        # publishes a join spec; it never spawns or supervises them — a
        # quiet remote channel is degradation, not a death.
        self.remote_workers = int(getattr(cfg.actor, "remote_workers", 0))
        # Elastic headroom (actor.max_workers; autopilot scale-up): the
        # global ε-ladder partition is carved over local_capacity wids AT
        # CONSTRUCTION, so a worker grown post-start claims a wid whose
        # actor slice was reserved from step zero — growth and retirement
        # never move a running worker's slice.  max_workers=0 keeps the
        # pre-elastic layout bit-for-bit (capacity == num_workers).
        self.local_capacity = max(
            self.num_workers, int(getattr(cfg.actor, "max_workers", 0) or 0)
        )
        self.total_workers = self.local_capacity + self.remote_workers
        self._queue_size = int(queue_size)
        self._ring_bytes = int(
            ring_bytes if ring_bytes is not None else cfg.actor.xp_ring_bytes
        )
        self._drain_budget = int(
            drain_budget_bytes if drain_budget_bytes is not None
            else cfg.actor.xp_drain_budget_bytes
        )
        # Experience transport backend (runtime/transport.py): the shm
        # ring by default — bit-for-bit the pre-seam path — or TCP
        # channels carrying the identical framed records.  Param
        # distribution follows the backend: the shared seqlock buffer
        # (shm) or delta/full frames on the experience connections (tcp,
        # NetParamStore).
        self._transport = make_transport(
            cfg, self.total_workers, self._ring_bytes, self._drain_budget
        )
        # Central inference (actor.inference=central): workers select
        # actions against the serving tier.  Without the local fallback
        # they are PARAMLESS — no seqlock buffer, no per-connection param
        # fan-out, store=None (the runtime substitutes a plain host
        # ParamStore for the serving tier's reload source); with
        # inference_fallback=local the normal param channel stays up so
        # outage steps can serve from the cached snapshot.
        self._central = cfg.actor.inference == "central"
        self._paramless = (
            self._central and cfg.actor.inference_fallback != "local"
        )
        self.inference_by_worker: dict = {}
        if self._paramless:
            self.buffer = None
            self.store = None
        elif self._transport.kind == "tcp":
            self.buffer = None
            self.store = NetParamStore(self._transport)
        else:
            if shm_capacity is None:
                # Size from the actual serialized template + headroom.
                from ape_x_dqn_tpu.utils.serialization import tree_to_bytes

                _, _, template = network_and_template(cfg)
                shm_capacity = len(tree_to_bytes(jax.device_get(template)))
                shm_capacity += shm_capacity // 4 + 4096
            self.buffer = SharedParamBuffer(shm_capacity)
            self.store = SharedMemoryParamStore(self.buffer)
        self._ctx = mp.get_context("spawn")
        # Experience rides one shm ring PER WORKER INCARNATION (replaced on
        # respawn): the ring is SIGKILL-safe by construction — no locks, a
        # kill mid-record leaves a detectably torn tail — but a fresh ring
        # per incarnation keeps the salvage accounting exact and the
        # respawned worker's stream seq-clean from record zero.  The
        # mp.Queue survives only as a CONTROL channel (done/error/episode
        # stats): low-volume, and its round-5 SIGKILL hazard (a worker
        # killed mid-put strands the queue's shared write lock) is confined
        # by the same per-incarnation replacement discipline.
        self._queues: dict = {}
        self._rings: dict = {}  # wid -> channel (ShmRing | NetChannel)
        self.transport = TransportStats()
        self._full_waits_base = 0  # full_waits of retired incarnations
        self.stop_event = self._ctx.Event()
        self._cfg_dict = to_dict(cfg)
        self._quantum = quantum or cfg.actor.flush_every
        self._procs: List = []
        self.actor_steps = 0
        self.episodes: List[tuple] = []
        self.last_versions = {}   # worker_id -> param version in latest chunk
        self.finished_workers = set()
        self.final_steps = {}     # worker_id -> fleet steps at clean "done"
        self.worker_errors = {}   # FATAL errors (restart budget exhausted)
        self.max_restarts = int(max_restarts)
        self.restarts = 0
        self._steps_by_worker: dict = {}      # cumulative, across restarts
        self._reported_errors: dict = {}      # wid -> last error message
        self._attempt: dict = {}              # wid -> spawn attempt count
        self._dead_since: dict = {}           # wid -> first-seen-dead time
        self._salvaged: list = []             # chunks drained pre-respawn
        self._silent_death_grace_s = 10.0
        # Supervision seams (runtime/supervisor.FleetSupervisor).  With a
        # policy attached, respawn timing/budget decisions are ITS —
        # exponential backoff + crash-loop quarantine replace the blunt
        # max_restarts fatal; without one, legacy max_restarts semantics
        # hold.  Either way the respawn_min_interval_s floor stands: a
        # deterministic startup crash must not spin the pool at fork speed.
        self.respawn_policy = None
        self.quarantined: set = set()         # written-off workers
        # Elastic state (grow/retire — the autopilot's actor actuators).
        self.retired: set = set()             # cleanly drained wids
        self._retire_events: dict = {}        # wid -> mp Event (live inc.)
        self._spawned_local: set = set()      # local wids ever spawned
        self.grows = 0
        self.retires = 0
        self._death_pending: dict = {}        # wid -> error, awaiting respawn
        self._last_spawn: dict = {}           # wid -> spawn time
        self._min_respawn_interval = float(cfg.actor.respawn_min_interval_s)
        # Observability: one shm stats block per worker incarnation (slots
        # + flight-recorder event ring, readable after SIGKILL —
        # obs/shm_stats); poll() sweeps them into a cached per-worker
        # snapshot, and _salvage_incarnation turns a dead incarnation's
        # block into a post-mortem record.
        self._stats_blocks: dict = {}
        self._stats_prev: dict = {}      # wid -> (t, env_steps, steps_s)
        self._worker_snap: dict = {}
        self._worker_snap_t = 0.0
        self.postmortems: List[dict] = []
        self._postmortem_dir = postmortem_dir
        # Per-host exploration component (multi-host SPMD: each host's
        # workers must not duplicate another host's streams).
        self._seed_base = int(seed_base)

    def _spawn(self, wid: int, budget: int):
        attempt = self._attempt.get(wid, 0)
        self._attempt[wid] = attempt + 1
        self._last_spawn[wid] = time.monotonic()
        if wid in self._queues:
            self._salvage_incarnation(wid)
        self._spawned_local.add(wid)
        self._retire_events[wid] = self._ctx.Event()
        self._queues[wid] = self._ctx.Queue(maxsize=self._queue_size)
        self._rings[wid] = self._transport.make_channel(wid, attempt)
        xp_spec = self._transport.endpoint(self._rings[wid], wid, attempt)
        if self.buffer is not None:
            param_spec = {"kind": "shm", "name": self.buffer.name,
                          "capacity": self.buffer.capacity}
        elif self.store is not None:
            param_spec = {"kind": "net"}
        else:
            param_spec = {"kind": "none"}   # central-paramless worker
        self._stats_prev.pop(wid, None)  # fresh incarnation: rate resets
        try:
            self._stats_blocks[wid] = WorkerStatsBlock(
                slots=WORKER_SLOTS,
                event_depth=max(16, getattr(
                    getattr(self.cfg, "obs", None), "recorder_depth", 64
                )),
            )
            stats_name = self._stats_blocks[wid].name
        except Exception:  # noqa: BLE001 — stats must not block a spawn
            stats_name = None
        p = self._ctx.Process(
            target=_worker_main,
            args=(wid, self._cfg_dict, self.total_workers, param_spec,
                  xp_spec, self._queues[wid], self.stop_event,
                  budget, self._quantum, attempt, self._seed_base,
                  self.cfg.actor.worker_nice, stats_name,
                  self._retire_events[wid]),
            daemon=True,
        )
        p.start()
        return p

    def _salvage_incarnation(self, wid: int) -> None:
        """Round-5 salvage discipline, on the shm transport: drain every
        FULLY-COMMITTED record out of the dead incarnation's ring (a kill
        mid-record leaves a torn tail the commit word detects — counted,
        never delivered), drain its control queue, then retire both.  The
        respawn gets a fresh ring, so its stream restarts seq-clean."""
        self._drain_control(self._queues[wid])
        ring = self._rings.pop(wid, None)
        ring_post: dict = {}
        if ring is not None:
            salvaged = 0
            while True:
                rec = ring.read_next()
                if rec is None:
                    break
                self._salvaged.append(self._decode_record(wid, rec))
                salvaged += 1
            torn = ring.torn_tail()
            self.transport.count_salvage(salvaged, torn=torn)
            self._full_waits_base += ring.full_waits
            ring_post = {
                "salvaged_records": salvaged,
                "torn_tail": bool(torn),
                "started": ring.started,
                "committed": ring.committed,
                "full_waits": ring.full_waits,
            }
            ring.close()
            ring.unlink()
            self._transport.drop_channel(wid, ring)
        # The dead incarnation's shm stats block is the post-mortem: final
        # slot values + the flight recorder's last events — readable even
        # after SIGKILL (the whole reason the block lives in /dev/shm).
        blk = self._stats_blocks.pop(wid, None)
        post = {
            "worker": wid,
            "attempt": self._attempt.get(wid, 1) - 1,
            "ring": ring_post,
        }
        if blk is not None:
            try:
                post["stats"] = blk.snapshot()
                events, ev_torn = blk.recent_events()
                post["events"] = events
                post["events_torn"] = ev_torn
            except Exception as e:  # noqa: BLE001 — salvage best-effort
                post["stats_error"] = f"{type(e).__name__}: {e}"
            blk.close()
            blk.unlink()
        self.postmortems.append(post)
        if self._postmortem_dir:
            path = write_postmortem(
                self._postmortem_dir, f"worker{wid}", "salvage", post
            )
            if path:
                post["path"] = path
        old = self._queues.pop(wid, None)
        if old is not None:
            try:  # release the pipe fds now, not at gc (256-worker budget)
                old.close()
            except Exception:  # noqa: BLE001 — dead-writer queue teardown
                pass

    def _drain_control(self, q, limit: int = 4096) -> None:
        import queue as queue_mod

        for _ in range(limit):
            try:
                self._dispatch(q.get_nowait())
            except queue_mod.Empty:
                return
            except Exception:  # torn pickle from a killed mid-put writer
                return

    def shm_accounting(self) -> dict:
        """Live fd/shm usage of the transport (logged by the fleet tools;
        the config-side planning twin is ``config.transport_budget``).
        tcp mode holds no rings and no param buffer in /dev/shm — only
        the per-worker stats blocks remain shm segments there."""
        import os as _os

        try:
            n_fds = len(_os.listdir("/proc/self/fd"))
        except OSError:
            n_fds = -1
        shm_mode = self._transport.kind == "shm"
        return {
            "transport": self._transport.kind,
            "shm_segments": (
                ((1 if self.buffer is not None else 0) + len(self._rings)
                 if shm_mode else 0)
                + len(self._stats_blocks)
            ),
            "ring_bytes_each": self._ring_bytes if shm_mode else 0,
            "ring_bytes_total": (
                self._ring_bytes * len(self._rings) if shm_mode else 0
            ),
            "param_buffer_bytes": (
                self.buffer.capacity if self.buffer is not None else 0
            ),
            "process_fds": n_fds,
        }

    def net_stats(self) -> dict:
        """The obs ``net`` section (tcp backend: bytes/s, frames,
        reconnects, torn frames, param fan-out cost per push) — empty
        dict on the shm backend, so emit/obs surfaces stay unchanged
        there."""
        return self._transport.stats()

    @property
    def transport_kind(self) -> str:
        return self._transport.kind

    def worker_stats(self, max_age_s: float = 0.5) -> dict:
        """Per-worker sweep of the shm stats blocks — env steps (+ a
        parent-derived steps/s), ε-ladder slice, chunk accounting, param
        version, heartbeat age, ring occupancy.  Cached for ``max_age_s``
        so the poll-cadence sweep stays O(workers) struct reads, and keyed
        by str(wid) for JSON stability on the /varz + emit surfaces."""
        now = time.monotonic()
        if self._worker_snap and now - self._worker_snap_t < max_age_s:
            return self._worker_snap
        out: dict = {}
        for wid, blk in list(self._stats_blocks.items()):
            try:
                snap = blk.snapshot()
            except Exception:  # noqa: BLE001 — a closing block mid-sweep
                continue
            ring = self._rings.get(wid)
            if ring is not None:
                snap["ring_backlog_bytes"] = max(
                    0, ring.committed_bytes - ring.bytes_read
                )
                snap["ring_full_waits"] = ring.full_waits
            prev = self._stats_prev.get(wid)
            if prev is not None and now - prev[0] >= 0.2:
                dt = now - prev[0]
                rate = max(0.0, snap["env_steps"] - prev[1]) / dt
                snap["env_steps_s"] = round(rate, 1)
                self._stats_prev[wid] = (now, snap["env_steps"], rate)
            elif prev is not None:
                snap["env_steps_s"] = round(prev[2], 1)
            else:
                snap["env_steps_s"] = 0.0
                self._stats_prev[wid] = (now, snap["env_steps"], 0.0)
            p = self._procs[wid] if wid < len(self._procs) else None
            snap["alive"] = bool(p.is_alive()) if p is not None else False
            out[str(wid)] = snap
        self._worker_snap = out
        self._worker_snap_t = now
        return out

    def _gate_shm_budget(self, new_rings: int,
                         include_param_buffer: bool) -> None:
        """fd/shm budget gate: fail loudly BEFORE spawning workers whose
        rings cannot fit /dev/shm (256 workers × ring_bytes is real
        money).  tcp mode allocates no rings — experience bytes live in
        kernel socket buffers — so only the shm backend gates here.  The
        SAME arithmetic gates the fleet start and every post-start
        ``grow`` (one more ring against the live free space)."""
        import os as _os

        if self._transport.kind != "shm":
            return
        need = new_rings * self._ring_bytes + (
            self.buffer.capacity
            if include_param_buffer and self.buffer is not None else 0
        )
        try:
            st = _os.statvfs("/dev/shm")
            free = st.f_bavail * st.f_frsize
        except OSError:
            return
        if need > free:
            raise RuntimeError(
                f"experience-transport shm budget {need} bytes exceeds "
                f"/dev/shm free space {free} — lower actor.xp_ring_bytes "
                f"or actor.num_workers"
            )

    def start(self, stagger_s: Optional[float] = None):
        """Spawn all workers, optionally throttled (``stagger_s`` seconds
        between spawns — at 256 workers an unthrottled start piles every
        child's jax import onto the host at once)."""
        stagger = (stagger_s if stagger_s is not None
                   else self.cfg.actor.spawn_stagger_s)
        self._gate_shm_budget(self.num_workers, include_param_buffer=True)
        for w in range(self.num_workers):
            self._procs.append(self._spawn(w, self.cfg.actor.T))
            if stagger and w + 1 < self.num_workers:
                time.sleep(stagger)
        if self.remote_workers:
            self.register_remote_workers()

    def register_remote_workers(self, path: Optional[str] = None) -> str:
        """Reserve channels for the ``actor.remote_workers`` externally-
        launched workers and publish the join spec (atomic tmp+rename
        JSON) that ``tools/host_join.py`` consumes: one endpoint spec per
        remote wid (host/port/per-run token/attempt + the wire-efficiency
        knobs), the full run config, and the global partition arithmetic,
        so a whole host attaches with one command and its actors land on
        exactly the slices this fleet reserved for them.

        Remote wids are never spawned or supervised here — their channels
        ride the normal poll sweep (reconnects handled by NetChannel),
        and a silent remote worker is degradation the operator sees on
        ``net.connections < net.expected``, not a pool fatal."""
        if self._transport.kind != "tcp":
            raise RuntimeError(
                "remote workers require actor.transport=tcp"
            )
        path = path or self.cfg.actor.remote_join_path
        if not path:
            raise RuntimeError("actor.remote_join_path is empty")
        specs = []
        for k in range(self.remote_workers):
            # Remote wids sit ABOVE the whole local capacity (spawned +
            # growable), so elastic growth never collides with a slice a
            # remote host already claimed.
            wid = self.local_capacity + k
            if wid not in self._rings:
                self._attempt[wid] = 1   # attempt 0 is the joinable one
                self._rings[wid] = self._transport.make_channel(wid, 0)
            specs.append(self._transport.endpoint(self._rings[wid], wid, 0))
        import json as _json

        doc = {
            "cfg": self._cfg_dict,
            "num_workers_total": self.total_workers,
            "num_local_workers": self.num_workers,
            "quantum": self._quantum,
            "seed_base": self._seed_base,
            "budget": int(self.cfg.actor.T),
            "specs": specs,
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            _json.dump(doc, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path

    # -- elastic grow/retire (the autopilot's actor-fleet actuators) -------

    def live_workers(self) -> List[int]:
        """Local wids currently contributing capacity: spawned, not
        retired, not quarantined, not finished/fatal (a booting respawn
        still counts — its slice is claimed)."""
        # Frozen copies: the autopilot thread reads this while the pump
        # thread mutates the sets (CPython set iteration is not safe
        # against concurrent adds).
        spawned = set(self._spawned_local)
        out = set(self.retired) | set(self.quarantined) \
            | set(self.worker_errors) | set(self.finished_workers)
        return sorted(spawned - out)

    def grow_candidates(self) -> List[int]:
        """Reserved local wids a ``grow`` could activate right now:
        never-spawned headroom plus cleanly-retired wids (fresh
        incarnation, SAME ε-ladder slice) — quarantined and fatal wids
        stay written off."""
        live = set(self.live_workers())

        def _settled(w: int) -> bool:
            # A retiring wid is reusable only once its old incarnation
            # fully drained: process exited AND ring/queue reclaimed by
            # the supervise sweep — never spawn over a live drain.
            if w < len(self._procs) and self._procs[w].is_alive():
                return False
            return w not in self._rings and w not in self._queues

        return sorted(
            w for w in range(self.local_capacity)
            if w not in live and w not in self.quarantined
            and w not in self.worker_errors and _settled(w)
            and max(0, self.cfg.actor.T - self._steps_by_worker.get(w, 0))
            > 0
        )

    def grow(self, n: int = 1, stagger_s: Optional[float] = None
             ) -> List[int]:
        """Activate up to ``n`` reserved wids post-start: the SAME spawn
        path as ``start()`` (fresh ring + stats block, remaining-budget
        arithmetic, stagger between spawns, /dev/shm gate per ring) on
        wids whose actor slices were carved at construction — growth
        never reshuffles a running worker's ε-ladder slice."""
        stagger = (stagger_s if stagger_s is not None
                   else self.cfg.actor.spawn_stagger_s)
        grown: List[int] = []
        for wid in self.grow_candidates():
            if len(grown) >= n:
                break
            self._gate_shm_budget(1, include_param_buffer=False)
            if grown and stagger:
                time.sleep(stagger)
            # A regrown wid sheds its retired/finished state; budget is
            # whatever actor.T it has not yet consumed.
            self.retired.discard(wid)
            self.finished_workers.discard(wid)
            self._death_pending.pop(wid, None)
            self._dead_since.pop(wid, None)
            budget = max(
                0, self.cfg.actor.T - self._steps_by_worker.get(wid, 0)
            )
            p = self._spawn(wid, budget)
            if wid < len(self._procs):
                self._procs[wid] = p
            else:
                # grow_candidates yields ascending wids, so _procs stays
                # index-addressable by wid (the supervise/stats contract).
                assert wid == len(self._procs)
                self._procs.append(p)
            self.grows += 1
            grown.append(wid)
        return grown

    def retire(self, wid: Optional[int] = None) -> Optional[int]:
        """Retire one worker via CLEAN DRAIN — never SIGKILL: its
        per-incarnation retire event ends the collect loop at the next
        quantum boundary, the worker flushes its committed chunks and
        exits through the normal "done" path, and the pool drains the
        ring before reclaiming it (supervise's retired sweep).  Default
        target is the HIGHEST live wid (scale-down walks the ladder top
        down, so the longest-lived slices keep exploring)."""
        live = self.live_workers()
        if wid is None:
            if not live:
                return None
            wid = live[-1]
        if wid not in live:
            return None
        self.retired.add(wid)
        self.retires += 1
        ev = self._retire_events.get(wid)
        if ev is not None:
            ev.set()
        return wid

    def set_drain_budget(self, budget_bytes: int) -> int:
        """Tune the per-poll byte drain budget live (the autopilot's
        ring-occupancy actuator; clamped to the config floor)."""
        self._drain_budget = max(64 << 10, int(budget_bytes))
        return self._drain_budget

    @property
    def drain_budget_bytes(self) -> int:
        return self._drain_budget

    def supervise(self) -> None:
        """Respawn dead workers (SURVEY §5 failure detection: actors are
        stateless modulo ε/seed, so recovery is respawn + param re-pull —
        the process-mode twin of _ActorWorker._supervise).  A worker that
        exited without a clean "done" — a reported exception OR a silent
        death (crash, OOM-kill) — restarts with its REMAINING step budget.

        Respawn TIMING and BUDGET are policy: with a supervisor attached
        (``respawn_policy`` — runtime/supervisor.FleetSupervisor), each
        death is reported once and respawns wait out the policy's
        exponential backoff; a crash-looping worker is QUARANTINED (ring
        salvaged, fleet shrinks, run continues).  Without one, legacy
        semantics: immediate respawns until ``max_restarts``, then the
        next death is fatal (worker_errors stops the pipeline).  Both
        paths honor the ``actor.respawn_min_interval_s`` floor — a
        deterministic startup crash can never spin the pool."""
        if self.stop_event.is_set():
            return
        now = time.monotonic()
        for wid, p in enumerate(self._procs):
            if wid in self.retired:
                # Clean drain in progress: never respawned.  Once the
                # process exited, salvage reclaims the ring/queue/stats
                # block (committed records drain into the next poll; a
                # cleanly-retired ring has no torn tail).
                if not p.is_alive() and wid in self._queues:
                    self._salvage_incarnation(wid)
                continue
            if wid in self.finished_workers or wid in self.worker_errors \
                    or wid in self.quarantined:
                continue
            if wid not in self._death_pending:
                if p.is_alive():
                    continue
                # A zero-exit death is normally a clean "done" (or a
                # reported error) whose message is still queued — poll()
                # will classify it.  Only a grace-period timeout turns an
                # unexplained zero-exit into a silent death (e.g. the final
                # queue put itself failed), so a clean finisher is never
                # spuriously respawned nor recorded as a fatal error.
                if p.exitcode == 0 and wid not in self._reported_errors:
                    first = self._dead_since.setdefault(wid, now)
                    if now - first < self._silent_death_grace_s:
                        continue
                self._dead_since.pop(wid, None)
                err = self._reported_errors.pop(
                    wid, f"worker exited silently (exitcode {p.exitcode})"
                )
                budget = max(
                    0, self.cfg.actor.T - self._steps_by_worker.get(wid, 0)
                )
                if budget == 0:
                    # Budget exhausted = a clean finish whatever the exit
                    # shape — no respawn, no restart credit consumed.
                    self.finished_workers.add(wid)
                    continue
                if self.respawn_policy is not None:
                    if self.respawn_policy.on_worker_death(wid, err) \
                            == "quarantine":
                        self._quarantine(wid)
                        continue
                elif self.restarts >= self.max_restarts:
                    self.worker_errors[wid] = err
                    continue
                self._death_pending[wid] = err
            # Death recorded; respawn when the interval floor AND the
            # policy's backoff (if any) have both elapsed.
            if now - self._last_spawn.get(wid, 0.0) \
                    < self._min_respawn_interval:
                continue
            if self.respawn_policy is not None:
                verdict = self.respawn_policy.decide_respawn(wid)
                if verdict == "wait":
                    continue
                if verdict == "quarantine":
                    self._quarantine(wid)
                    continue
            self._death_pending.pop(wid, None)
            budget = max(
                0, self.cfg.actor.T - self._steps_by_worker.get(wid, 0)
            )
            self.restarts += 1
            self._procs[wid] = self._spawn(wid, budget)

    def _quarantine(self, wid: int) -> None:
        """Write a crash-looping worker off: salvage its last incarnation
        (committed records delivered, torn tail counted, post-mortem
        written) and shrink the fleet — the run continues without it."""
        self._death_pending.pop(wid, None)
        self.quarantined.add(wid)
        if wid in self._queues:
            self._salvage_incarnation(wid)

    def publish(self, params) -> int:
        if self.store is None:
            return -1    # central-paramless fleet: nothing to fan out
        return self.store.publish(params)

    def set_inference_endpoint(self, host: str, port: int,
                               token: int) -> None:
        """Patch the resolved central-inference endpoint into the worker
        config BEFORE spawn (auto mode binds an ephemeral port after the
        config was frozen).  Also lands in the remote join spec, so
        host_join workers dial the same endpoint."""
        a = self._cfg_dict["actor"]
        a["inference_host"] = str(host)
        a["inference_port"] = int(port)
        a["inference_token"] = int(token)

    def inference_stats(self) -> dict:
        """Fleet-wide central-inference accounting (the obs ``inference``
        section's client half): per-worker counter sums + merged
        round-trip percentiles from the shipped histogram states."""
        from ape_x_dqn_tpu.serving.central import aggregate_inference_stats

        return aggregate_inference_stats(
            self.inference_by_worker.values(),
            mode="central" if self._central else "local",
        )

    @property
    def finished(self) -> bool:
        # Elastic-aware completion: every wid still expected to produce
        # (ever spawned, not retired by the autopilot) has settled.  With
        # no grow/retire this is exactly the legacy num_workers check.
        if not self._spawned_local:
            return False
        active = set(self._spawned_local) - set(self.retired)
        settled = (set(self.finished_workers) | set(self.worker_errors)
                   | set(self.quarantined))
        return all(w in settled for w in active)

    def poll(self, max_items: int = 64, timeout: float = 0.0,
             max_bytes: Optional[int] = None,
             with_meta: bool = False) -> List[tuple]:
        """One batched sweep over every live worker's ring (bounded by
        ``max_items`` chunks and the byte drain budget) plus the control
        queues; returns [(priorities, transitions), ...] — or, with
        ``with_meta``, [(priorities, transitions, meta), ...] where meta
        carries the wire envelope's observability fields (worker id,
        ``sent_t``, lineage ``trace_id``).  Episode stats / completion /
        errors update pool state, and the worker stats blocks are swept
        into the cached per-worker snapshot, as side effects."""
        import queue as queue_mod

        # Accept/handshake/param-push pump (tcp backend; shm no-op): new
        # worker connections route to their channels on the poll cadence.
        self._transport.pump()
        self.worker_stats()  # throttled shm sweep rides the poll cadence
        out = list(self._salvaged)
        self._salvaged.clear()
        budget = max_bytes if max_bytes is not None else self._drain_budget
        deadline = time.monotonic() + timeout if timeout else None
        while len(out) < max_items and budget > 0:
            got = False
            for q in list(self._queues.values()):  # control: low volume
                try:
                    self._dispatch(q.get_nowait())
                    got = True
                except queue_mod.Empty:
                    continue
                except Exception:  # noqa: BLE001 — torn pickle from a killed mid-put writer; the record is unrecoverable by design
                    continue
            for wid, ring in list(self._rings.items()):
                # Round-robin fairness: a few records per ring per pass, so
                # one hot worker cannot starve the sweep.
                for _ in range(4):
                    if len(out) >= max_items or budget <= 0:
                        break
                    rec = ring.read_next()
                    if rec is None:
                        break
                    got = True
                    budget -= len(rec)
                    out.append(self._decode_record(wid, rec))
            if not got:
                if not out and deadline and time.monotonic() < deadline:
                    time.sleep(min(0.01, timeout))
                    continue
                break
        if with_meta:
            return out
        return [(prio, trans) for prio, trans, _ in out]

    def _decode_record(self, wid: int, payload: bytes) -> tuple:
        """One ring record → (priorities, transitions, meta) + pool
        accounting.  Arrays are zero-copy read-only views over the
        record's own buffer (already out of the ring), handed straight to
        replay ingest; meta is the envelope's observability triple."""
        (kind, version, sent_t, steps, source, chunk_seq, prev_frames,
         trace_id, arrays) = decode_chunk(payload)
        self.last_versions[wid] = version
        self.actor_steps += steps
        # Fleet steps = chunk rows / actors-in-worker; tracked so a
        # respawn only gets the worker's REMAINING actor.T budget.
        n_w = self._worker_width(wid)
        self._steps_by_worker[wid] = (
            self._steps_by_worker.get(wid, 0) + steps // max(n_w, 1)
        )
        self.transport.record_chunk(
            len(payload), time.monotonic() - sent_t, steps
        )
        meta = {"wid": wid, "sent_t": sent_t, "trace_id": trace_id}
        prio = arrays.pop("prio")
        if kind == DXP:
            from ape_x_dqn_tpu.types import DedupChunk

            return (prio, DedupChunk(
                source=source, chunk_seq=chunk_seq, prev_frames=prev_frames,
                **arrays,
            ), meta)
        return (prio, self._NStepTransition(**arrays), meta)

    def trace_events(self, max_per_worker: int = 32) -> List[dict]:
        """Cross-tier trace spans recorded by LIVE workers, swept off
        their shm event rings (the flight recorder mirrors every
        ``trace_chunk`` / ``trace_span`` event there, so worker-side
        spans are readable without any new plumbing — and survive a
        SIGKILL exactly like the rest of the block).  ``trace_chunk``
        (the actor's flush of a traced chunk) is lifted into a
        zero-duration ``act`` span: the hop that pins the WORKER's pid
        onto the timeline."""
        spans: List[dict] = []
        for wid, blk in list(self._stats_blocks.items()):
            try:
                events, _torn = blk.recent_events(max_per_worker)
                pid = blk.pid
            except Exception:  # noqa: BLE001 — a dying block reads as no spans, never a sweep crash
                continue
            for ev in events:
                tid = ev.get("trace_id")
                if not tid:
                    continue
                if ev.get("kind") == "trace_chunk":
                    t = float(ev.get("t", 0.0))
                    spans.append({
                        "trace_id": int(tid), "hop": "act", "pid": pid,
                        "t0_s": t, "t1_s": t, "dur_ms": 0.0, "wid": wid,
                    })
                elif ev.get("kind") == "trace_span":
                    spans.append(
                        {k: v for k, v in ev.items() if k not in ("kind",)}
                    )
        return spans

    def transport_stats(self) -> dict:
        """Experience-transport metrics snapshot: ingest bytes/s, chunk
        latency percentiles, ring-full backpressure events (live rings plus
        retired incarnations), torn-record salvage counts."""
        s = self.transport.summary()
        s["transport"] = self._transport.kind
        s["ring_full_waits"] = self._full_waits_base + sum(
            r.full_waits for r in self._rings.values()
        )
        s["rings"] = len(self._rings)
        s["ring_bytes"] = self._ring_bytes
        return s

    def _dispatch(self, msg):
        """Apply one control-channel message to pool state."""
        kind = msg[0]
        if kind == "episodes":
            self.episodes.extend(msg[2])
        elif kind == "inference":
            # Latest-wins per worker: each snapshot is cumulative for the
            # incarnation, so the newest one subsumes the rest.
            self.inference_by_worker[msg[1]] = msg[2]
        elif kind == "done":
            self.finished_workers.add(msg[1])
            # Cumulative fleet steps across incarnations (each "done"
            # reports its own incarnation's count).  Restart-free runs
            # land on actor.T exactly (the budget clamp in _worker_main);
            # after a restart the respawn budget comes from chunk-based
            # accounting, so the total is clamp-accurate only to the
            # flush cadence.
            self.final_steps[msg[1]] = (
                self.final_steps.get(msg[1], 0) + msg[2]
            )
        elif kind == "error":
            # Recorded for supervise(): respawnable until the restart
            # budget runs out, fatal after.
            self._reported_errors[msg[1]] = msg[2]
        return None

    def _worker_width(self, wid: int) -> int:
        """Actors in worker ``wid``'s slice of the global set."""
        lo, hi = worker_slice(
            wid, self.cfg.actor.num_actors, self.total_workers
        )
        return hi - lo

    def stop(self, join_timeout: float = 15.0):
        self.stop_event.set()
        # Drain while joining: ring writers abort on the stop event by
        # themselves (write() polls it), but the final control puts and any
        # committed chunks should land in accounting before teardown.
        deadline = time.monotonic() + join_timeout
        for p in self._procs:
            while p.is_alive() and time.monotonic() < deadline:
                self.poll(max_items=256)
                p.join(timeout=0.1)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
        self.poll(max_items=256)  # last committed records + "done" messages
        # Release every shm segment and control-queue fd on ALL exit paths
        # (the 256-worker fd/shm budget depends on it).  Rings retired here
        # still settle their salvage accounting: a worker killed just
        # before stop leaves a torn tail nobody respawned past — it must
        # land on the transport's torn counter, not vanish with the unlink
        # (the chaos soak's every-tear-detected invariant).
        for wid in list(self._rings):
            ring = self._rings.pop(wid)
            self._full_waits_base += ring.full_waits
            if ring.torn_tail():
                self.transport.count_salvage(0, torn=True)
            ring.close()
            ring.unlink()
            self._transport.drop_channel(wid, ring)
        for wid in list(self._queues):
            try:
                self._queues.pop(wid).close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        for wid in list(self._stats_blocks):
            blk = self._stats_blocks.pop(wid)
            blk.close()
            blk.unlink()
        self._transport.close()
        if self.buffer is not None:
            self.buffer.close()


class ProcessActorWorker:
    """``_ActorWorker``-compatible front for a ProcessActorPool, so
    AsyncPipeline drives thread and process actor modes through one
    interface (start/join/drain_episodes/finished/error/heartbeat/
    actor_steps/restarts).

    A pump thread drains the pool's experience queue into the runtime's
    sink (host replay or the fused learner's staging buffer) — the
    analogue of the reference's dedicated drain process (main.py:21-25,
    57-58), as a thread because the sink lives in this process.
    """

    def __init__(self, pool: "ProcessActorPool", sink, logger=None, fps=None,
                 stop_event: Optional[threading.Event] = None,
                 lineage=None):
        from ape_x_dqn_tpu.actors import EpisodeStat

        self._EpisodeStat = EpisodeStat
        self.pool = pool
        self._sink = sink
        # Experience-lineage hook (obs/lineage.LineageTracker): fed with
        # the replay slots each chunk landed in (the host-replay sink
        # returns them) plus the envelope's trace id / send time.
        self._lineage = lineage
        self._logger = logger
        self._fps = fps
        self._stop = threading.Event()
        # The runtime's stop event: set on worker death so the learner loop
        # (and warmup poll) exits promptly instead of training against a
        # frozen replay until its step target / timeout (mirrors
        # _ActorWorker._supervise's permafail behavior).
        self._external_stop = stop_event
        self.error: Optional[BaseException] = None
        self.heartbeat = time.monotonic()
        self._ep_lock = threading.Lock()
        self.episodes: List = []
        self._thread = threading.Thread(
            target=self._pump, name="process-actor-pump", daemon=True
        )

    @property
    def finished(self) -> bool:
        return self.pool.finished and not self.pool.worker_errors

    @property
    def actor_steps(self) -> int:
        return self.pool.actor_steps

    @property
    def restarts(self) -> int:
        """Worker process respawns (the pool's supervisor counter)."""
        return self.pool.restarts

    def start(self):
        self.pool.start()
        self._thread.start()

    def join(self, timeout: float = 30.0):
        self._stop.set()
        self._thread.join(timeout)
        self.pool.stop()

    def drain_episodes(self) -> List:
        with self._ep_lock:
            out, self.episodes = self.episodes, []
        return out

    def _pump(self):
        while not self._stop.is_set():
            self.pool.supervise()
            items = self.pool.poll(max_items=64, timeout=0.05,
                                   with_meta=True)
            sink_trace = getattr(self._sink, "takes_trace", False)
            for prio, trans, meta in items:
                if sink_trace:
                    # Remote-replay sink: the chunk's wire-envelope trace
                    # id rides the add RPC (the cross-tier timeline's
                    # wire → shard hop).
                    idx = self._sink(prio, trans, meta["trace_id"])
                else:
                    idx = self._sink(prio, trans)
                if self._fps is not None:
                    self._fps.add(len(prio))
                if self._lineage is not None and idx is not None:
                    # Host-replay sinks return the slot indices written —
                    # the lineage hand-off point (fused sinks return None:
                    # HBM slots never surface to the host).
                    self._lineage.on_ingest(
                        idx, t_act=meta["sent_t"],
                        trace_id=meta["trace_id"], wid=meta["wid"],
                    )
            if items:
                self.heartbeat = time.monotonic()
            if self.pool.episodes:
                with self._ep_lock:
                    self.episodes.extend(
                        self._EpisodeStat(a, r, l)
                        for (a, r, l) in self.pool.episodes
                    )
                self.pool.episodes.clear()
            if self.pool.worker_errors and self.error is None:
                self.error = RuntimeError(
                    f"actor worker(s) died: {self.pool.worker_errors}"
                )
                if self._logger is not None:
                    self._logger.log("actor/worker_errors",
                                     len(self.pool.worker_errors))
                if self._external_stop is not None:
                    self._external_stop.set()
                self.pool.stop_event.set()
                # Keep draining: surviving workers may be blocked in
                # xp_queue.put on the bounded queue and only see the stop
                # event once their put completes — returning here would
                # deadlock them until the join-time drain.
            if self.pool.finished:
                return
