"""The async Ape-X pipeline: actors ∥ replay ∥ learner on one host.

This is the reference's architectural idea — three concurrently-running
stages decoupled by the replay (reference main.py:46-58) — rebuilt on the
TPU-native transport stack instead of manager-proxy RPC:

  actor thread(s) ──chunks──▶ PrioritizedReplay ◀──sample── feeder thread
        ▲                                                        │ device_put
        └──── ParamStore (versioned snapshots) ◀── learner ◀── PrefetchQueue

  * **Actor stage**: one thread per fleet (each fleet is already a batched
    vector of actors — one jitted forward per fleet step).  Exceptions
    respawn the fleet (actors are stateless modulo ε/seed — SURVEY §5
    failure detection: "recovery is respawn + param re-pull"); heartbeats
    are exported as metrics.
  * **Replay stage**: the buffer's own lock discipline (batched ops only);
    no drain process — writers call straight into the ring, which is the
    reference's queue+drain collapsed into one bounded structure with
    backpressure by construction (the reference's manager queue is
    unbounded — SURVEY §3.4).
  * **Learner stage**: runs on the caller thread.  Batches arrive staged on
    device by the PrefetchQueue (host sample + transfer hidden behind the
    running step); priority write-back is deferred by one step so the host
    never blocks on the in-flight step's outputs; params publish to the
    store at the capped rate.

Stop/join semantics: ``run()`` drives the learner to a step target, then
signals actors and joins them (the reference crashes at exactly this point —
main.py:61 joins a list).
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

import numpy as np

from ape_x_dqn_tpu.actors import EpisodeStat
from ape_x_dqn_tpu.config import ApexConfig
from ape_x_dqn_tpu.runtime.components import build_components
from ape_x_dqn_tpu.runtime.infeed import PrefetchQueue
from ape_x_dqn_tpu.runtime.param_store import ParamStore
from ape_x_dqn_tpu.utils.memory import trim_malloc
from ape_x_dqn_tpu.utils.metrics import MetricLogger, RateCounter
from ape_x_dqn_tpu.utils import profiling


class _AsyncPublisher:
    """Publish param snapshots off the learner thread.

    A publish = device_get (~13 MB for the conv net) + wire serialization
    + checksum + shared-memory write — host work that stretches to seconds
    when worker processes contend for the host's cores.  The learner
    thread only snapshots
    the params with a cheap device-side copy (one tiny dispatch, no sync)
    and hands the copy here; this thread does the slow host work.  A 1-slot
    latest-wins mailbox: if publishing lags, intermediate versions are
    skipped — exactly the versioned-snapshot semantics the store already
    has (actors always want the newest, reference actor.py:189-191).
    """

    def __init__(self, store):
        self._store = store
        self._pending = None
        self._busy = False
        self._cv = threading.Condition()
        self._stop = False
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._loop, name="param-publisher", daemon=True
        )
        self._thread.start()

    def submit(self, device_params) -> None:
        with self._cv:
            self._pending = device_params  # latest wins
            self._cv.notify()

    def flush(self, timeout: float = 120.0) -> bool:
        """Block until the newest submitted snapshot has been published.
        Returns False if work is still outstanding at the timeout — the
        caller must surface that (a silently unpublished final snapshot
        leaves actors on stale params with no error)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while (self._pending is not None or self._busy) \
                    and time.monotonic() < deadline:
                self._cv.wait(timeout=0.1)
            return self._pending is None and not self._busy

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=30.0)

    def _loop(self) -> None:
        import jax

        while True:
            with self._cv:
                while self._pending is None and not self._stop:
                    self._cv.wait()
                if self._pending is None and self._stop:
                    return
                params, self._pending = self._pending, None
                self._busy = True
            try:
                self._store.publish(jax.device_get(params))
            except BaseException as e:  # noqa: BLE001 — surfaced by runtime
                self.error = e
            finally:
                with self._cv:
                    self._busy = False
                    self._cv.notify_all()


class _ActorWorker:
    """Supervised actor-fleet thread with respawn-on-crash."""

    def __init__(self, comps, store: ParamStore, stop: threading.Event,
                 logger: MetricLogger, fps: RateCounter,
                 max_restarts: int = 3, quantum: Optional[int] = None,
                 sink=None, seed_base: int = 0, lineage=None,
                 trace_sample_rate: float = 0.0, selector_factory=None):
        self._comps = comps
        # Central inference (actor.inference=central): a factory
        # (fleet, incarnation) -> CentralSelector replaces local action
        # selection — the fleet never syncs params (unless the selector's
        # fallback does, on its own).
        self._selector_factory = selector_factory
        # Lineage (obs/lineage): thread-mode chunks have no wire envelope,
        # so the trace id is stamped HERE, at the sink hand-off — t_act and
        # t_ingest coincide (the flush happened microseconds ago in
        # collect), which is truthful for in-process actors.
        self._lineage = lineage
        self._trace_rate = float(trace_sample_rate)
        import random as _random

        self._trace_rng = _random.Random(0x0B5 ^ seed_base)
        self._store = store
        self._stop = stop
        self._logger = logger
        self._fps = fps
        self._max_restarts = max_restarts
        self._quantum = quantum or comps.cfg.actor.flush_every
        # Where chunks go: the host replay by default, or any
        # (priorities, transitions) callable (the fused learner's staging
        # sink in device-replay mode).  A remote replay's add is an RPC —
        # hand it the chunk's trace id so the hop joins the lineage
        # timeline (takes_trace marks the wider signature).
        if sink is not None:
            self._sink = sink
        elif getattr(comps.replay, "remote", False):
            def _traced_sink(prio, trans, trace_id=0):
                return comps.replay.add(prio, trans, trace_id=trace_id)

            _traced_sink.takes_trace = True
            self._sink = _traced_sink
        else:
            self._sink = lambda prio, trans: comps.replay.add(prio, trans)
        self.restarts = 0
        # Fleet seed base: nonzero under multi-host SPMD so each host's
        # actors explore distinct streams while the MODEL seed (cfg.seed)
        # stays identical everywhere — replicated param placement asserts
        # cross-process equality.
        self._seed_base = seed_base
        self.finished = False  # clean exit (actor.T reached), not a crash
        self.fleet_steps = 0   # total fleet steps across incarnations
        self.heartbeat = time.monotonic()
        # Newest param snapshot the fleet has adopted (-1 before the first
        # sync) — the thread-mode twin of the workers' shm param_version.
        self.param_version = -1
        self.episodes: List[EpisodeStat] = []
        self._ep_lock = threading.Lock()
        self.actor_steps = 0
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._supervise, name="actor-fleet", daemon=True
        )

    def start(self):
        self._thread.start()

    def join(self, timeout: float = 30.0):
        self._thread.join(timeout)

    def drain_episodes(self) -> List[EpisodeStat]:
        with self._ep_lock:
            out, self.episodes = self.episodes, []
        return out

    def _supervise(self):
        # Cumulative fleet steps across incarnations: actor.T bounds TOTAL
        # env steps, so a respawned fleet only gets the remaining budget
        # (round-1 advisor finding: a fresh step_count per incarnation let
        # crashy fleets exceed T).
        steps_done = 0
        while not self._stop.is_set():
            fleet = None
            selector = None
            try:
                fleet = self._comps.make_fleet(
                    seed_offset=self._seed_base + self.restarts
                )
                if self._selector_factory is not None:
                    selector = self._selector_factory(fleet, self.restarts)
                else:
                    fleet.sync_params(self._store)
                self._run_fleet(fleet, self._comps.cfg.actor.T - steps_done,
                                selector=selector)
                self.fleet_steps = steps_done + fleet.step_count
                # Distinguish "actor.T exhausted" from "told to stop".
                self.finished = not self._stop.is_set()
                return  # clean stop
            except Exception as e:
                if self._stop.is_set():
                    # A stop raced the central select (typed
                    # InferenceUnavailable) or teardown: clean exit, not
                    # a crash — no restart credit consumed.
                    if fleet is not None:
                        self.fleet_steps = steps_done + fleet.step_count
                    return
                if fleet is not None:
                    steps_done += fleet.step_count
                    self.fleet_steps = steps_done
                self.restarts += 1
                self._logger.log("actor/restarts", self.restarts)
                if self.restarts > self._max_restarts:
                    self.error = e
                    self._stop.set()
                    return
                time.sleep(0.1)

    def _run_fleet(self, fleet, max_steps: int, selector=None):
        while not self._stop.is_set() and fleet.step_count < max_steps:
            # Clamp the final quantum so the fleet lands on max_steps
            # exactly — actor.T bounds TOTAL env steps, and an unclamped
            # collect could overshoot by quantum-1 steps per incarnation.
            quantum = min(self._quantum, max_steps - fleet.step_count)
            chunks, stats = fleet.collect(
                quantum,
                param_source=self._store if selector is None else None,
                selector=selector,
            )
            for chunk in chunks:
                trace_id = 0
                if self._lineage is not None and self._trace_rate \
                        and self._trace_rng.random() < self._trace_rate:
                    trace_id = self._trace_rng.getrandbits(63) or 1
                if getattr(self._sink, "takes_trace", False):
                    idx = self._sink(chunk.priorities, chunk.transitions,
                                     trace_id)
                else:
                    idx = self._sink(chunk.priorities, chunk.transitions)
                self.actor_steps += chunk.actor_steps
                self._fps.add(chunk.actor_steps)
                if self._lineage is not None and idx is not None:
                    self._lineage.on_ingest(idx, trace_id=trace_id)
            if stats:
                with self._ep_lock:
                    self.episodes.extend(stats)
            self.heartbeat = time.monotonic()
            self.param_version = fleet.param_version
            # Arena hygiene (see utils/memory): the collect loop's obs
            # allocation stream otherwise grows RSS without bound.
            trim_malloc()


# Fused calls dispatched and not yet forced, at most.  With no cap the
# learner enqueues K-step programs back-to-back and every thread actor's
# policy_step waits behind the whole backlog — actors starve (measured: FPS
# drops ~30x).  Two keeps the next call queued behind the running one, so
# the device never waits for the host (the benchmark's feed holds the same
# two and idles the device under 1% — PERF.md section 5), and bounds an
# actor's wait to about one call.
_FUSED_INFLIGHT = 2


class AsyncPipeline:
    """One-host async runtime.  ``run()`` blocks the caller as the learner."""

    def __init__(
        self,
        cfg: ApexConfig,
        logger: Optional[MetricLogger] = None,
        log_every: int = 500,
        prefetch_depth: int = 2,
        max_actor_restarts: int = 3,
        eval_every: int = 0,
        eval_episodes: int = 10,
    ):
        self.comps = build_components(cfg)
        self.cfg = self.comps.cfg
        self.logger = logger or MetricLogger()
        self.log_every = log_every
        self.stop_event = threading.Event()
        # 30 s windows: chunk arrivals are bursty (one flush of a 512-actor
        # fleet is ~8k transitions), so narrow windows see 0 or 1 bursts.
        self._fps = RateCounter(window_s=30.0)
        self._steps_rate = RateCounter(window_s=30.0)
        # The expert layers' counters of the last logged fused call
        # (StepMetrics.routing), for the JSONL line and /varz; {} for a
        # network without such layers.
        self._routing: dict = {}
        self._attention: dict = {}    # StepMetrics.attention, likewise
        self._scan: dict = {}         # StepMetrics.scan, likewise
        self._delta: dict = {}        # StepMetrics.delta, likewise
        # Per-stage wall-clock accumulators (SURVEY §5 tracing subsystem):
        # µs/step per pipeline stage, exported in every metrics emit.
        self.timers = profiling.StageTimer()
        self._prefetch_depth = prefetch_depth
        self.fused = None
        self.mesh = None
        # SPMD process identity (multi-host; 1/0 when jax.distributed was
        # never initialized) — set unconditionally so every publish /
        # checkpoint / seed path below is host-aware in every mode.
        import jax

        self._n_proc = jax.process_count()
        self._proc_idx = jax.process_index()
        if self._n_proc > 1:
            # Multi-host SPMD sanity (round-3 advisor): with data_parallel=1
            # each host would silently train an independent, divergent model
            # on a B/n batch; and the fused HBM path has no multi-host story
            # (per-host rings + concurrent same-dir checkpoint saves) —
            # reject both shapes at init instead of corrupting a run.
            if self.cfg.learner.device_replay:
                raise ValueError(
                    "learner.device_replay=True is single-process only — "
                    "multi-host SPMD runs use the host-replay path with "
                    "learner.data_parallel spanning all hosts' devices"
                )
            if self.cfg.learner.data_parallel <= 1:
                raise ValueError(
                    f"jax.process_count()={self._n_proc} requires "
                    "learner.data_parallel > 1: the mesh must span every "
                    "host's devices, or each host trains an independent "
                    "model on a fractional batch"
                )
            if self.cfg.learner.replay_sample_size % self._n_proc:
                raise ValueError(
                    "learner.replay_sample_size must divide by "
                    f"jax.process_count()={self._n_proc}"
                )
        sink = None
        if self.cfg.learner.device_replay:
            self.fused = self.comps.make_fused_learner()
            if self.comps.restored_path is not None:
                # Second half of resume: the train state was restored in
                # build_components; the HBM ring reloads here, after the
                # fused learner exists (a learner restart must not lose
                # the buffer).  load_replay_leg:
                # the per-step npz snapshot when one exists, else the
                # committed incremental chain (checkpoint_incremental
                # saves write no npz at all).
                from ape_x_dqn_tpu.utils.checkpoint import load_replay_leg
                from ape_x_dqn_tpu.utils.metrics import emit_event

                if load_replay_leg(
                    self.comps.restored_path, self.fused
                ) is None:
                    emit_event(
                        "checkpoint_restore_missing_replay",
                        path=self.comps.restored_path,
                        consequence="fused ring resumes empty",
                    )
            sink = self.fused.add_chunk
            self.train_step = None
        elif self.cfg.learner.data_parallel > 1:
            # Mesh data-parallel learner (BASELINE.md config 4): the same
            # loop below, with the step jitted over the mesh, infeed batches
            # sharded in _place, and the replicated params published as-is.
            # Under multi-host SPMD (jax.distributed initialized, every
            # host running this same program) the mesh spans all hosts'
            # devices: each host samples its B/n share from its LOCAL
            # replay, the global batch assembles host rows onto host
            # devices (parallel.place_local_batch — no cross-host batch
            # traffic), the all-reduce crosses DCN inside the step, and
            # each host restamps only its own priority rows.
            self.train_step, sharded_state, self.mesh = (
                self.comps.make_sharded_train_step()
            )
            self.comps.state = sharded_state
        else:
            self.train_step = self.comps.make_train_step()
        # --- observability layer (ape_x_dqn_tpu/obs) ----------------------
        # Registry + health are always built (they are cheap dicts); the
        # HTTP exporter only when obs.export_port says so.  Lineage runs on
        # the host-replay path only — the fused HBM replay never surfaces
        # sample indices to the host (that is its whole point), so there
        # lineage ends at ingest.
        from ape_x_dqn_tpu.obs import (
            FlightRecorder,
            Health,
            LineageTracker,
            MetricsRegistry,
        )

        ocfg = self.cfg.obs
        self.obs_registry = MetricsRegistry()
        # Host-process extensions (serve.py's attached serving tier, a
        # mounted socket front end, ...) can ride the trainer's periodic
        # JSONL emit as their own named section — register_jsonl_section.
        self._jsonl_sections: dict = {}
        # Host-memory gauge (utils/memory.rss_bytes): the flat-RSS
        # observable for hours-scale soaks — malloc_trim runs at emit
        # cadence; this is the number that proves it held.
        from ape_x_dqn_tpu.utils.memory import rss_bytes

        self.obs_registry.gauge(
            "host/rss_bytes", help="resident set size of this process"
        ).set_fn(rss_bytes)
        # Tiered-replay instruments (replay/tiered.py): live only when the
        # host replay runs with a hot frame budget.  The named series ride
        # /varz + /metrics as gauges; the full tier dict (incl. the
        # fault-latency histogram summary) is the `replay_tier` provider
        # section and the JSONL emit's `replay_tier` key.
        self._tier_evictor = None
        _tier_replay = self.comps.replay
        if _tier_replay is not None and getattr(_tier_replay, "tier", None) \
                is not None:
            from ape_x_dqn_tpu.replay.tiered import TierEvictor

            tier = _tier_replay.tier
            self.obs_registry.gauge(
                "replay/spilled_bytes",
                help="bytes written to the replay cold tier",
            ).set_fn(lambda: tier.spilled_bytes)
            self.obs_registry.gauge(
                "replay/fault_reads",
                help="cold-span fault reads on the sample path",
            ).set_fn(lambda: tier.fault_reads)
            self.obs_registry.gauge(
                "replay/hot_bytes",
                help="resident frame bytes in the replay hot tier",
            ).set_fn(lambda: tier.hot_bytes)
            self.obs_registry.register_provider(
                "replay_tier", _tier_replay.tier_stats
            )
            # Background evictor: spills ride this thread, never the
            # learner's critical path (the checkpoint writer's discipline).
            self._tier_evictor = TierEvictor(_tier_replay)
        self.health = Health(stale_after_s=ocfg.heartbeat_stale_s)
        # Replay-as-a-service client (replay/service.py): its degradation
        # surface rides the registry (`replay_svc` provider on /varz +
        # the JSONL section below) and /healthz — a down shard is a
        # DEGRADED component and buffered write-backs, never a wedge.
        self._remote_replay = None
        if self.comps.replay is not None \
                and getattr(self.comps.replay, "remote", False):
            self._remote_replay = self.comps.replay
            self.obs_registry.register_provider(
                "replay_svc", self._remote_replay.stats
            )
            self.health.register("replay_svc", self._remote_replay.age_s)
            self.register_jsonl_section(
                "replay_svc", self._remote_replay.stats
            )
        self._postmortem_dir = self._resolve_postmortem_dir()
        self.recorder = FlightRecorder(
            "trainer", depth=ocfg.recorder_depth
        )
        self.recorder.add_snapshot_provider(
            "varz", self.obs_registry.snapshot
        )
        self._lineage = None
        if self.fused is None:
            self._lineage = LineageTracker(
                self.cfg.replay.capacity, emit=self.logger.event
            )
        # --- supervision tier (runtime/supervisor) ------------------------
        # The policy layer over every recovery signal: typed worker
        # respawn/backoff/quarantine (attached to the process pool below),
        # the learner-progress watchdog (attached after the run mode is
        # known), serving staleness (serve.py attaches), and the
        # fallback-restore counter (degraded restores recorded before this
        # point — build_components' replay leg — are drained here).
        self.supervisor = None
        if self.cfg.supervisor.enabled:
            from ape_x_dqn_tpu.runtime.supervisor import FleetSupervisor

            self.supervisor = FleetSupervisor(
                self.cfg.supervisor, registry=self.obs_registry,
                health=self.health, emit=self.logger.event,
                seed=self.cfg.seed,
            )
        self._chaos = None
        if self.cfg.actor.mode == "process":
            # Actors in CPU-only worker processes: params travel as
            # serialized snapshots through shared memory, experience through
            # one SIGKILL-safe shm ring per worker incarnation
            # (runtime/process_actors.py + runtime/shm_ring.py — the
            # reference's N-process actor layout, main.py:50-54).
            from ape_x_dqn_tpu.runtime.process_actors import (
                ProcessActorPool,
                ProcessActorWorker,
            )

            pool = ProcessActorPool(
                self.cfg, num_workers=self.cfg.actor.num_workers,
                seed_base=self._proc_idx * 7919,  # per-host exploration
                postmortem_dir=self._postmortem_dir,
            )
            if pool.store is None:
                # Central-paramless fleet (actor.inference=central, no
                # local fallback): workers receive actions, not params —
                # the plain host store exists only to feed the serving
                # tier's hot reload (and the param_version metric).
                self.store = ParamStore(
                    self._params_host(self.comps.state.params)
                )
            else:
                self.store = pool.store
                # _params_host: under multi-host the state may already be
                # placed over the global mesh — publish the local replica.
                self.store.publish(
                    self._params_host(self.comps.state.params)
                )
            if sink is not None:
                proc_sink = sink
            elif self._remote_replay is not None:
                # Remote replay: the add RPC carries the chunk's wire-
                # envelope trace id, so a traced experience's first RPC
                # hop lands on the cross-tier timeline.
                def proc_sink(prio, trans, trace_id=0):
                    return self.comps.replay.add(prio, trans,
                                                 trace_id=trace_id)

                proc_sink.takes_trace = True
            else:
                def proc_sink(prio, trans):
                    return self.comps.replay.add(prio, trans)
            self.worker = ProcessActorWorker(
                pool,
                proc_sink,
                logger=self.logger,
                fps=self._fps,
                stop_event=self.stop_event,
                lineage=self._lineage,
            )
            self.obs_registry.register_provider(
                "workers", pool.worker_stats
            )
            self.obs_registry.register_provider(
                "xp_transport", pool.transport_stats
            )
            if pool.transport_kind == "tcp":
                # Network transport observables (runtime/net.py): bytes/s,
                # frames, reconnects, torn frames, param fan-out cost —
                # the `net` section on /varz, /metrics and the JSONL emit.
                self.obs_registry.register_provider("net", pool.net_stats)
            if self.supervisor is not None:
                self.supervisor.attach_pool(pool)
        else:
            self.store = ParamStore(self._params_host(self.comps.state.params))
            self.worker = _ActorWorker(
                self.comps, self.store, self.stop_event, self.logger,
                self._fps, max_restarts=max_actor_restarts, sink=sink,
                seed_base=self._proc_idx * 7919,
                lineage=self._lineage,
                trace_sample_rate=ocfg.trace_sample_rate,
                selector_factory=(
                    self._make_central_selector
                    if self.cfg.actor.inference == "central" else None
                ),
            )
        # --- central inference (actor.inference=central) -------------------
        # SEED-style paramless actors: action selection lives in the
        # serving tier's micro-batcher.  Auto mode (inference_port=0)
        # hosts the PolicyServer + ServingNetServer in THIS process —
        # the serving fleet and the training fleet are literally the
        # same process tree — and patches the resolved endpoint + run
        # token into the worker config before spawn; a nonzero port
        # names an external ServingNetServer or ServingRouter.
        self._central_server = None
        self._central_net = None
        self._central_selectors: list = []
        self._central_endpoint = None
        if self.cfg.actor.inference == "central":
            self._build_central_serving()
            self.obs_registry.register_provider(
                "inference", self._inference_section
            )
            self.register_jsonl_section(
                "inference", self._inference_section
            )
        self.obs_registry.register_provider("learner", self._learner_varz)
        # Cross-tier trace spans (obs/lineage.TraceSpanLog): everything
        # THIS process (and its swept workers) recorded, in one place for
        # the fleet aggregator to collect into e2e timelines.
        self.obs_registry.register_provider(
            "trace_spans", self._trace_spans
        )
        self.obs_registry.register_provider(
            "stage_us", self.timers.us_per_call
        )
        # The launch's partition and the compiles since it ended
        # (utils/profiling.LaunchLog): whole on /varz, the recompiles on
        # every periodic record.
        self.obs_registry.register_provider(
            "launch", lambda: profiling.launch.varz()
        )
        self.register_jsonl_section(
            "launch", lambda: profiling.launch.since_launch()
        )
        if self._lineage is not None:
            self.obs_registry.register_provider(
                "lineage", self._lineage.summary
            )
            # Cross-host monotone-clock guard: sent_t stamps from a
            # skewed remote clock are clamped at ingest, never emitted as
            # negative spans; this counts how often that fired.
            self.obs_registry.gauge(
                "lineage/clock_skew_clamped",
                help="cross-host act timestamps clamped to ingest time",
            ).set_fn(lambda: self._lineage.clock_skew_clamped)
        # /healthz components (the exporter's liveness view): the learner
        # loop beats inline; the ingest pump already tracks a heartbeat.
        self.health.register(
            "ingest", lambda: time.monotonic() - self.worker.heartbeat
        )
        # Off-thread publisher (single-process): the learner snapshots
        # params with one cheap device-side copy; device_get + serialize +
        # store write happen on the publisher thread (see _AsyncPublisher —
        # measured seconds per publish under worker CPU contention).
        # Multi-host keeps the synchronous per-leaf local-replica path, and
        # so does a network whose second copy would not fit beside the
        # learner (_copy_fits).
        self._publisher = None
        self._param_copy = None
        if self._n_proc == 1 and self._copy_fits(self.comps.state.params):
            import jax.numpy as jnp

            self._param_copy = jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t)
            )
            self._publisher = _AsyncPublisher(self.store)
        self._learner_step = self.comps.learner_step
        if self.fused is not None:
            self._sample = None
        else:
            self._sample = self.comps.make_sampler(
                lambda: self._learner_step,
                sample_size=(
                    self.cfg.learner.replay_sample_size // self._n_proc
                ),
                rng_salt=self._proc_idx * 7919,
            )
        self.episode_returns: List[float] = []
        # Periodic greedy evaluation (ε≈0.001, no emission — the scoring
        # path for the "median human-normalized score" north-star metric;
        # evaluation.py).  Runs on the learner thread at the cadence, so
        # eval time is learner downtime; 0 disables.
        self._eval_every = int(eval_every)
        self._eval_episodes = int(eval_episodes)
        self._next_eval = self._eval_every
        self._evaluator = None
        self.eval_scores: List[float] = []
        # Incremental async replay checkpointing (utils/checkpoint_inc):
        # the replay leg leaves the inline full np.savez — the learner
        # thread only snapshots cursors + the span written since the last
        # save; a writer thread does the device_get/compression/IO and the
        # manifest-last commit.  Constructed AFTER the restore above so the
        # first save chains onto a resumed run's committed manifest
        # (counters match its chain_mark) instead of forcing a fresh base.
        # Built per host with this host's shard suffix under multi-host.
        self._ckpt_inc = None
        if self.cfg.learner.checkpoint_every \
                and self.cfg.learner.checkpoint_incremental:
            from ape_x_dqn_tpu.utils.checkpoint import replay_shard_suffix
            from ape_x_dqn_tpu.utils.checkpoint_inc import (
                IncrementalCheckpointer,
            )

            self._ckpt_inc = IncrementalCheckpointer(
                self.cfg.learner.checkpoint_dir,
                self.fused if self.fused is not None else self.comps.replay,
                suffix=replay_shard_suffix(),
                base_every=self.cfg.learner.checkpoint_base_every,
                compress=self.cfg.learner.checkpoint_compress,
            )
            self.obs_registry.register_provider(
                "ckpt", self._ckpt_inc.stats
            )
            # The writer thread has no beat cadence (saves are sparse), so
            # liveness is structural: thread alive and no recorded error.
            self.health.register(
                "ckpt_writer",
                lambda: 0.0 if (
                    self._ckpt_inc.error is None
                    and (self._ckpt_inc._thread is None
                         or self._ckpt_inc._thread.is_alive())
                ) else float("inf"),
            )
        # The exporter thread last, once every provider is registered.
        # Explicit ports bind on host 0 only (multi-host SPMD would
        # collide); port 0 (ephemeral) is per-host safe.
        from ape_x_dqn_tpu.obs import ObsServer, TraceOnDemand

        self.trace_on_demand = TraceOnDemand(
            step_fn=lambda: self._learner_step,
            steps=self.cfg.obs.trace_steps,
            out_dir=self.cfg.obs.trace_dir,
        )
        self.obs_server = None
        self.obs_port = None
        if self.cfg.obs.export_port is not None and (
            self._proc_idx == 0 or self.cfg.obs.export_port == 0
        ):
            self.obs_server = ObsServer(
                self.obs_registry, self.health,
                port=self.cfg.obs.export_port,
                trace_hook=self.trace_on_demand.trigger,
            )
            self.obs_port = self.obs_server.port
            self.logger.event(
                "obs_exporter", port=self.obs_port,
                url=self.obs_server.url,
            )
        if self.supervisor is not None:
            # Learner watchdog: progress is the step count — a learner
            # wedged inside a dispatch or a force does not advance it.  One
            # silent deadline raises a degraded event, a second declares
            # the run wedged (event + /healthz 503 via the supervisor
            # component).
            self.supervisor.attach_learner(
                progress_fn=lambda: self._learner_step,
            )
        if self.cfg.chaos.enabled:
            # Chaos monkey (obs/chaos): a seeded fault schedule against
            # THIS run's own workers and checkpoint chain.  Built last so
            # its counters and provider ride the same registry scrape.
            from ape_x_dqn_tpu.obs.chaos import ChaosMonkey

            self._chaos = ChaosMonkey(
                self.cfg.chaos, registry=self.obs_registry,
                emit=self.logger.event,
            )
            pool = getattr(self.worker, "pool", None)
            ckpt_dirs = (
                [self.cfg.learner.checkpoint_dir]
                if self.cfg.learner.checkpoint_every else []
            )
            self._chaos.attach(pool=pool, ckpt_dirs=ckpt_dirs)
        # --- elastic autopilot (autopilot.*; ROADMAP item 3's actuation
        # loop) -------------------------------------------------------------
        # The controller needs the sensor layer IN-PROCESS: a
        # FleetAggregator whose "trainer" endpoint is this registry's own
        # snapshot (no HTTP round trip; identical merge arithmetic), with
        # the config-declared SLO rules subscribed straight into the
        # controller's event queue.  The actor loop actuates on this
        # process's own pool; a serving fleet is attached by the driver
        # (``pipe.autopilot.attach_serving(...)`` + replica endpoints on
        # ``pipe.autopilot_aggregator``) — capacity topology is the
        # deployment's, not the trainer's.
        self.autopilot = None
        self.autopilot_aggregator = None
        if self.cfg.autopilot.enabled:
            from ape_x_dqn_tpu.autopilot import (
                ActorPoolActuator,
                AutopilotController,
            )
            from ape_x_dqn_tpu.obs.fleet import (
                FleetAggregator,
                engine_from_config,
            )

            slo = engine_from_config(self.cfg.obs, emit=self.logger.event)
            self.autopilot_aggregator = FleetAggregator(
                scrape_interval_s=self.cfg.obs.fleet_scrape_interval_s,
                scrape_timeout_s=self.cfg.obs.fleet_scrape_timeout_s,
                window_s=self.cfg.obs.fleet_slo_window_s,
                slo=slo,
            )
            self.autopilot_aggregator.add_local(
                "trainer", self.obs_registry.snapshot, kind="trainer"
            )
            # Flight-data recorder (obs/timeline.py): every sweep lands
            # one delta record on disk, and attaching REBUILDS the SLO
            # burn windows from the previous incarnation's tail — a
            # respawned trainer resumes its alarm state, no blind window.
            tl_dir = self._resolve_timeline_dir()
            if tl_dir is not None:
                from ape_x_dqn_tpu.obs.timeline import TimelineStore

                try:
                    self.autopilot_aggregator.attach_timeline(TimelineStore(
                        tl_dir,
                        max_bytes=self.cfg.obs.timeline_max_bytes,
                        segment_bytes=self.cfg.obs.timeline_segment_bytes,
                        tail_keep_s=self.cfg.obs.timeline_tail_keep_s,
                    ))
                    self.obs_registry.register_provider(
                        "timeline", self.autopilot_aggregator.timeline.stats
                    )
                except OSError as e:
                    # An unwritable dir degrades to no recorder — the
                    # sweep loop and the SLO engine still run.
                    self.logger.event("timeline_open_failed", error=str(e))
            self.autopilot = AutopilotController(
                self.cfg.autopilot,
                rollup_fn=self.autopilot_aggregator.rollup,
                emit=self.logger.event,
            )
            slo.subscribe(self.autopilot.on_slo_event)
            pool = getattr(self.worker, "pool", None)
            if pool is not None:
                self.autopilot.attach_actor(ActorPoolActuator(pool))
            self.obs_registry.register_provider(
                "autopilot", self.autopilot.state
            )
            self.register_jsonl_section("autopilot", self.autopilot.state)
        # --- fleet discovery plane (fleet.*) --------------------------------
        # Under ``fleet.discovery = "registry"`` the trainer hosts the
        # run-token-scoped membership registry: replay shards, serving
        # replicas and worker hosts JOIN over the announce wire
        # (F_FANN/F_FREP) instead of the driver plumbing ports through
        # files and pipes, and the in-process aggregator adopts
        # membership as its scrape-target truth.  The bound port + token
        # ride a JSONL event so drivers and tools can hand them to their
        # fleets (the endpoints file stays as the compat fallback).
        self.fleet_registry = None
        if self.cfg.fleet.discovery == "registry":
            import secrets

            from ape_x_dqn_tpu.fleet.registry import FleetRegistry

            self.fleet_registry = FleetRegistry(
                token=secrets.randbits(63) or 1,
                host=self.cfg.fleet.registry_host,
                port=self.cfg.fleet.registry_port,
                ttl_s=self.cfg.fleet.ttl_s,
                on_event=self.logger.event,
            ).serve()
            self.logger.event(
                "fleet_registry_listen",
                host=self.cfg.fleet.registry_host,
                port=self.fleet_registry.port,
                token=self.fleet_registry.token,
            )
            self.obs_registry.register_provider(
                "fleet_membership", self.fleet_registry.snapshot
            )
            if self.autopilot_aggregator is not None:
                self.autopilot_aggregator.bind_registry(self.fleet_registry)

    def _build_central_serving(self) -> None:
        """Resolve the central-inference endpoint: host an in-process
        serving tier when auto (port 0), else adopt the configured
        external endpoint (a ServingNetServer or ServingRouter)."""
        a, s = self.cfg.actor, self.cfg.serving
        host, port, token = (
            a.inference_host, int(a.inference_port), int(a.inference_token)
        )
        if port == 0:
            import secrets

            from ape_x_dqn_tpu.serving.net_server import ServingNetServer
            from ape_x_dqn_tpu.serving.server import PolicyServer

            if token == 0:
                token = secrets.randbits(63) or 1
            # First params come from the store's host snapshot, never from
            # the live train state: device_put of an on-device array is no
            # copy, and the fused learner donates the state's buffers on
            # its first call — the server would be left applying deleted
            # arrays until its first hot reload.
            server = PolicyServer(
                self.comps.network,
                param_source=self.store,
                max_batch=s.max_batch,
                max_wait_ms=s.max_wait_ms,
                queue_capacity=s.queue_capacity,
                reload_poll_s=s.reload_poll_s,
            )
            server.warmup(self.comps.obs_shape)
            server.start()
            net = ServingNetServer(
                server, host=host, port=0,
                max_request_bytes=s.max_request_bytes, run_token=token,
            ).start()
            self._central_server, self._central_net = server, net
            port = net.port
            self.health.register(
                "central_serving",
                lambda: time.monotonic() - server.batcher.heartbeat,
            )
            self.logger.event(
                "central_inference_listen", port=port, host=host
            )
        self._central_endpoint = (host, port, token)
        pool = getattr(self.worker, "pool", None)
        if pool is not None and hasattr(pool, "set_inference_endpoint"):
            pool.set_inference_endpoint(host, port, token)

    def _make_central_selector(self, fleet, incarnation: int = 0):
        """Thread-mode selector factory (one fleet per _ActorWorker
        incarnation): the same client/selector the process workers build
        from their config, dialing the resolved endpoint in-process."""
        from ape_x_dqn_tpu.serving.central import (
            CentralInferenceClient,
            CentralSelector,
            InferenceUnavailable,
        )

        a = self.cfg.actor
        host, port, token = self._central_endpoint
        client = CentralInferenceClient(
            host, port, wid=0, attempt=incarnation, token=token,
            codec=a.inference_codec, dedup=a.inference_dedup,
            inflight=a.inference_inflight, seed=self.cfg.seed,
            trace=self.cfg.obs.trace_sample_rate > 0,
        )
        fallback = None
        if a.inference_fallback == "local":
            def fallback(obs, step, _fleet=fleet):
                import jax

                _fleet.sync_params(self.store)
                if _fleet.params is None:
                    raise InferenceUnavailable("no param snapshot yet")
                acts, q = jax.device_get(_fleet._policy_step(
                    _fleet.params, obs, _fleet._epsilons, step
                ))
                return np.asarray(acts), np.asarray(q), _fleet.param_version
        sel = CentralSelector(
            client, np.asarray(fleet._epsilons), fleet.envs.num_actions,
            seed=self.cfg.seed + 77_000 + incarnation,
            timeout_s=a.inference_timeout_s,
            trace_sample_rate=self.cfg.obs.trace_sample_rate,
            fallback=fallback,
            should_stop=self.stop_event.is_set,
        )
        self._central_selectors = [sel]   # latest incarnation wins
        return sel

    def _trace_spans(self) -> dict:
        """The ``trace_spans`` /varz provider: cross-tier spans from
        every log this process owns — the remote-replay client's RPC
        hops, the in-process serving tier's server hops, thread-mode
        inference clients — plus the live workers' shm event rings
        (worker-pid ``act`` spans and central-inference client spans,
        swept without any extra IPC)."""
        spans: list = []
        recorded = 0
        logs = []
        if self._remote_replay is not None:
            logs.append(self._remote_replay.spans)
        if self._central_net is not None:
            logs.append(self._central_net.spans)
        for sel in self._central_selectors:
            logs.append(sel.client.spans)
        for log in logs:
            snap = log.snapshot()
            recorded += snap["recorded"]
            spans.extend(snap["spans"])
        pool = getattr(self.worker, "pool", None)
        if pool is not None and hasattr(pool, "trace_events"):
            worker_spans = pool.trace_events()
            recorded += len(worker_spans)
            spans.extend(worker_spans)
        return {"recorded": recorded, "spans": spans[-256:]}

    def _inference_section(self) -> dict:
        """The obs ``inference`` section (docs/METRICS.md "Inference
        schema"): the fleet-side client aggregate + the serving-side
        occupancy/freshness the trainer can see."""
        from ape_x_dqn_tpu.serving.central import aggregate_inference_stats

        pool = getattr(self.worker, "pool", None)
        if pool is not None and hasattr(pool, "inference_stats"):
            out = pool.inference_stats()
        else:
            out = aggregate_inference_stats(
                [s.stats(include_hist=True)
                 for s in self._central_selectors]
            )
            out.pop("rtt_state", None)
        # Freshness: publishes the newest reply version trails the store
        # by — 0 means actors act on the batcher's current params (the
        # staleness collapse central inference exists for).
        v = out.get("param_version", -1)
        out["version_lag"] = (
            max(0, self.store.version - v) if v >= 0 else None
        )
        occ = None
        if self._central_server is not None:
            hist = self._central_server.batcher.batch_hist
            total = sum(hist.values())
            if total:
                occ = round(
                    sum(k * c for k, c in hist.items()) / total, 2
                )
        out["batch_occupancy_mean"] = occ
        return out

    def _resolve_postmortem_dir(self) -> Optional[str]:
        """obs.postmortem_dir policy: explicit path wins; "auto" lands
        post-mortems under the checkpoint dir a checkpointed run already
        owns, and stays off otherwise (no stray dirs from ad-hoc runs)."""
        import os

        d = self.cfg.obs.postmortem_dir
        if d == "auto":
            if self.cfg.learner.checkpoint_every:
                return os.path.join(
                    self.cfg.learner.checkpoint_dir, "postmortem"
                )
            return None
        return d

    def _resolve_timeline_dir(self) -> Optional[str]:
        """obs.timeline_dir policy — the postmortem_dir discipline:
        explicit path wins; "auto" lands the flight-data recorder under
        the checkpoint dir a checkpointed run already owns, and stays
        off otherwise."""
        import os

        d = self.cfg.obs.timeline_dir
        if d == "auto":
            if self.cfg.learner.checkpoint_every:
                return os.path.join(
                    self.cfg.learner.checkpoint_dir, "timeline"
                )
            return None
        return d

    def _learner_varz(self) -> dict:
        """The learner section of every /varz scrape and /metrics flatten
        — the same numbers the JSONL emit carries, readable mid-emit."""
        out = {
            "step": self._learner_step,
            "steps_per_sec": round(self._steps_rate.rate(), 1),
            "actor_fps": round(self._fps.rate(), 1),
            "actor_steps": self.worker.actor_steps,
            "actor_restarts": self.worker.restarts,
            "param_version": self.store.version,
            "actor_heartbeat_age": round(
                time.monotonic() - self.worker.heartbeat, 3
            ),
        }
        try:
            out["replay_size"] = (
                self.fused.size if self.fused is not None
                else self.comps.replay.size()
            )
        except Exception:  # noqa: BLE001 — scrape must not crash
            pass
        if self._routing:
            out["routing"] = dict(self._routing)
        if self._attention:
            out["attention"] = dict(self._attention)
        if self._scan:
            out["scan"] = dict(self._scan)
        if self._delta:
            out["delta"] = dict(self._delta)
        return out

    def _maybe_eval(self):
        if not self._eval_every or self._learner_step < self._next_eval:
            return
        while self._next_eval <= self._learner_step:
            self._next_eval += self._eval_every
        from ape_x_dqn_tpu.evaluation import log_result, make_evaluator

        if self._evaluator is None:
            self._evaluator = make_evaluator(
                self.comps.env_fns, self.comps.network,
                env_name=self.cfg.env.name, seed=self.cfg.seed,
            )
        params = (
            self.fused.params_for_publish()
            if self.fused is not None
            else self._params_host(self.comps.state.params)
        )
        with self.timers.stage("eval"):
            res = self._evaluator.evaluate(
                params, episodes=self._eval_episodes
            )
        self.eval_scores.append(res.mean_score)
        log_result(self.logger, res)

    @staticmethod
    def _copy_fits(params) -> bool:
        """Whether a device-side copy of ``params`` is cheap beside the
        learner: under an eighth of the device's memory, where the device
        says how much it has.  The two sizes this was read at (TPU v5e,
        16.9 GB; chip_smoke.py's --lfm2moe and --laguna legs): 455 M
        parameters, 1.8 GB, fit beside their state and a fused call's
        temporaries; 737 M, 2.95 GB, do not, and there a publish reads the
        live parameters synchronously and stalls the learner 0.79 s
        (PERF.md, PR 32).  The eighth lies between the two and is no finer
        than that."""
        import jax

        leaves = jax.tree_util.tree_leaves(params)
        stats = next(iter(leaves[0].devices())).memory_stats() if leaves else None
        limit = (stats or {}).get("bytes_limit")
        return not limit or 8 * sum(x.nbytes for x in leaves) <= limit

    def _publish(self, params) -> None:
        if self._publisher is not None:
            # Surface publisher failures at the NEXT publish, not hours
            # later at end-of-run (actors would train against frozen
            # version-0 params the whole time).
            if self._publisher.error is not None:
                raise RuntimeError(
                    "param publisher failed"
                ) from self._publisher.error
            self._publisher.submit(self._param_copy(params))
        else:
            self.store.publish(self._params_host(params))

    def _finish_publishes(self) -> None:
        if self._publisher is not None:
            flushed = self._publisher.flush()
            if self._publisher.error is not None:
                raise RuntimeError(
                    "param publisher failed"
                ) from self._publisher.error
            if not flushed:
                raise RuntimeError(
                    "param publisher could not drain within its timeout — "
                    "the final snapshot was never published"
                )

    def _finish_checkpoints(self) -> None:
        """Success-path drain of the incremental checkpoint writer: an
        undrained final delta is silent replay loss on the next resume.
        flush() re-raises a writer-thread failure."""
        if self._ckpt_inc is not None and not self._ckpt_inc.flush():
            raise RuntimeError(
                "incremental checkpoint writer could not drain within its "
                "timeout — the final replay delta was never committed"
            )

    def _close_checkpoints(self) -> None:
        """finally-path close — best-effort so a teardown failure never
        masks the primary exception (the success path already surfaced
        writer errors via _finish_checkpoints)."""
        if self._ckpt_inc is not None:
            try:
                self._ckpt_inc.close(timeout=30.0)
            except Exception:  # noqa: BLE001 — exit-path teardown; writer errors surfaced via _finish_checkpoints
                pass

    def _write_back_priorities(self, idx, priorities) -> None:
        """Commit one step's deferred (indices, device priorities)."""
        with self.timers.stage("priority_writeback"):
            prio = self._priorities_host(priorities)
            if self._remote_replay is not None:
                # Remote replay: a traced experience among these slots
                # stamps the write-back RPC — the timeline's final hop.
                tids = (self._lineage.trace_ids_for(idx)
                        if self._lineage is not None else [])
                self.comps.replay.update_priorities(
                    idx, prio, trace_id=tids[0] if tids else 0
                )
            else:
                self.comps.replay.update_priorities(idx, prio)
        if self._lineage is not None:
            # The write-back forced the step's device work — its slots
            # are now TRAINED.
            self._lineage.on_trained(idx)

    def _launch_done(self, step: int) -> None:
        """The first result the host waited for ends the launch: one
        ``launch`` event with the partition (``LaunchLog.summary``); a
        compile from here on is a recompile."""
        if profiling.launch.done(step):
            self.logger.event("launch", **profiling.launch.summary(top=12))

    def _force_fused(self, metrics) -> None:
        """Force one fused call's completion (a host read of its last
        loss) and credit its steps to the completion-time rate."""
        float(np.asarray(metrics.loss[-1]))
        self._steps_rate.add(self.fused.steps_per_call)

    @property
    def learner_step(self) -> int:
        return self._learner_step

    def _wait_for_warmup(self, timeout: float, size_fn=None, tick=None):
        """Block until replay holds min_replay_mem_size transitions
        (reference learner.py:64-65's poll loop).  ``tick`` runs each poll
        (the fused mode ingests staged chunks with it)."""
        size_fn = size_fn or self.comps.replay.size
        deadline = time.monotonic() + timeout
        while size_fn() < self.cfg.learner.min_replay_mem_size:
            if tick is not None:
                tick()
            if self.stop_event.is_set():
                raise RuntimeError("actors stopped during warmup") from self.worker.error
            if self.worker.finished and size_fn() < self.cfg.learner.min_replay_mem_size:
                raise RuntimeError(
                    f"actors exhausted actor.T={self.cfg.actor.T} env steps "
                    f"with replay at {size_fn()} / "
                    f"{self.cfg.learner.min_replay_mem_size} — raise actor.T "
                    "or lower learner.min_replay_mem_size"
                )
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"replay warmup stalled at {size_fn()} / "
                    f"{self.cfg.learner.min_replay_mem_size}"
                )
            time.sleep(0.05)

    def run(
        self,
        learner_steps: Optional[int] = None,
        warmup_timeout: float = 600.0,
    ) -> dict:
        cfg = self.cfg
        target = learner_steps if learner_steps is not None else cfg.learner.total_steps
        if self.fused is not None:
            return self._run_fused(target, warmup_timeout)
        self._obs_run_start(target)
        self.worker.start()
        if self._tier_evictor is not None:
            self._tier_evictor.start()
            self.health.register(
                "tier_evictor",
                lambda: time.monotonic() - self._tier_evictor.heartbeat,
            )
        try:
            self._wait_for_warmup(warmup_timeout)
            with PrefetchQueue(
                self._sample,
                place_fn=self._place,
                depth=self._prefetch_depth,
            ) as queue:
                # (indices, device priorities) of the previous step: its
                # write-back runs one step behind, so the host read never
                # blocks on the step in flight.
                pending = None
                metrics = None
                state = self.comps.state
                while self._learner_step < target and not self.stop_event.is_set():
                    self.health.beat("learner")
                    with self.timers.stage("sample+place"):
                        host_indices, batch = queue.get()
                    if self._lineage is not None:
                        self._lineage.on_sample(host_indices)
                        if self._remote_replay is not None:
                            # A traced slot in this batch stamps the
                            # parked sample-RPC span post hoc (whether a
                            # sample hits a trace is only knowable here).
                            tids = self._lineage.trace_ids_for(host_indices)
                            if tids:
                                self._remote_replay.tag_sample_span(tids[0])
                    with self.timers.stage("step_dispatch"):
                        state, metrics = self.train_step(state, batch)
                    # Keep the live state visible on self so a mid-run
                    # exception never strands an advanced step counter with
                    # stale params (a ref assignment, no device sync).
                    self.comps.state = state
                    self._learner_step += 1
                    self._steps_rate.add(1)
                    if pending is not None:
                        self._write_back_priorities(*pending)
                        self._launch_done(self._learner_step - 1)
                    pending = (host_indices, metrics.priorities)
                    profiling.launch.step = self._learner_step
                    if self._learner_step % cfg.learner.publish_every == 0:
                        with self.timers.stage("publish"):
                            self._publish(state.params)
                    if (
                        cfg.learner.checkpoint_every
                        and self._learner_step % cfg.learner.checkpoint_every == 0
                    ):
                        with self.timers.stage("checkpoint"):
                            self._save_host_checkpoint(state)
                    self._maybe_eval()
                    if self._learner_step % self.log_every == 0:
                        self._emit(metrics)
                if pending is not None:
                    self._write_back_priorities(*pending)
            self._finish_publishes()
            self._finish_checkpoints()
        except BaseException as e:
            self._obs_fault(e)
            raise
        finally:
            self.stop_event.set()
            self.worker.join()
            if self._tier_evictor is not None:
                self._tier_evictor.stop()
            if self._publisher is not None:
                self._publisher.close()
            self._close_checkpoints()
            self._close_obs()
        if self.worker.error is not None:
            raise RuntimeError("actor worker died") from self.worker.error
        if self._tier_evictor is not None \
                and self._tier_evictor.error is not None:
            raise RuntimeError(
                "tier evictor died"
            ) from self._tier_evictor.error
        # Final emit carries the last step's metrics (one host sync) so the
        # returned record always has learner/loss — callers assert on it.
        return self._emit(metrics, final=True)

    def _run_fused(self, target: int, warmup_timeout: float) -> dict:
        """Device-replay mode: ingest staged actor chunks, then fused
        K-step calls — sample/train/restamp never leave HBM."""
        import numpy as np

        from ape_x_dqn_tpu.runtime.single_process import beta_schedule

        cfg = self.cfg
        fused = self.fused
        self._obs_run_start(target)
        self.worker.start()
        last_metrics = None
        inflight: list = []  # metrics of dispatched-but-unforced calls
        try:
            # Drain partial blocks once the actors are done — otherwise a
            # tail of < ingest_block staged rows can strand warmup below the
            # threshold even though enough transitions were collected
            # (round-2 advisor finding).
            with profiling.launch.span("ring_fill"):
                self._wait_for_warmup(
                    warmup_timeout,
                    size_fn=lambda: fused.size,
                    tick=lambda: fused.ingest_staged(
                        drain=self.worker.finished),
                )
            next_log = self._learner_step + self.log_every
            next_ckpt = (
                self._learner_step + cfg.learner.checkpoint_every
                if cfg.learner.checkpoint_every
                else None
            )
            while self._learner_step < target and not self.stop_event.is_set():
                self.health.beat("learner")
                with self.timers.stage("ingest"):
                    fused.ingest_staged(drain=self.worker.finished)
                beta = beta_schedule(
                    self._learner_step, cfg.learner.total_steps,
                    cfg.replay.is_exponent,
                )
                with self.timers.stage("fused_dispatch"):
                    last_metrics = fused.train(beta)
                inflight.append(last_metrics)
                if len(inflight) >= _FUSED_INFLIGHT:
                    # Force the oldest call with a tiny host read of its
                    # last loss.  steps_per_sec counts steps at FORCE time —
                    # dispatch runs ahead of the device, so counting at
                    # dispatch would report steps that haven't executed yet.
                    with self.timers.stage("force_oldest"):
                        self._force_fused(inflight.pop(0))
                    self._launch_done(self._learner_step)
                self._learner_step += fused.steps_per_call
                profiling.launch.step = self._learner_step
                self.comps.state = fused.state
                # Publish at most once per fused call — the cap
                # (publish_every) is finer than K, so every call qualifies;
                # a coarser cap than K publishes on the calls that cross it.
                if self._learner_step % max(
                    cfg.learner.publish_every, fused.steps_per_call
                ) < fused.steps_per_call:
                    with self.timers.stage("publish"):
                        self._publish(fused.params_for_publish())
                if next_ckpt is not None and self._learner_step >= next_ckpt:
                    with self.timers.stage("checkpoint"):
                        self._save_fused_checkpoint()
                    next_ckpt += cfg.learner.checkpoint_every
                self._maybe_eval()
                if self._learner_step >= next_log:
                    self._emit_fused(last_metrics)
                    next_log += self.log_every
            # Drain stragglers so the final rates/loss reflect completed
            # device work, not dispatched-but-unfinished calls.
            while inflight:
                self._force_fused(inflight.pop(0))
            self._finish_publishes()
            self._finish_checkpoints()
        except BaseException as e:
            self._obs_fault(e)
            raise
        finally:
            self.stop_event.set()
            self.worker.join()
            if self._publisher is not None:
                self._publisher.close()
            self._close_checkpoints()
            self._close_obs()
        if self.worker.error is not None:
            raise RuntimeError("actor worker died") from self.worker.error
        if last_metrics is not None:
            loss = np.asarray(last_metrics.loss)
            if not np.all(np.isfinite(loss)):
                raise FloatingPointError("non-finite loss in fused learner")
        return self._emit_fused(last_metrics, final=True)

    def _save_host_checkpoint(self, state) -> None:
        """Periodic host-replay save at the cadence.

        Full-sync mode: multi-host ordering — EVERY host saves its own
        replay shard FIRST, a barrier proves all shards are on disk, and
        only then does process 0 write state/ (the marker that makes the
        step dir restorable), so a restore can never see a committed
        checkpoint with missing shards.  The shard step comes from the same
        state the state-writer uses, keeping both sides of the dir name on
        one source of truth.

        Incremental mode (learner.checkpoint_incremental): the replay leg
        is this thread's bounded dirty-span snapshot handed to the writer
        thread — no npz, no barrier (the chain is its own independently
        manifest-committed artifact spanning steps; restore takes the
        newest committed manifest per shard, which may trail the state by
        up to one in-flight save — deltas chain, nothing is lost)."""
        from ape_x_dqn_tpu.utils.checkpoint import (
            replay_shard_suffix,
            save_checkpoint,
            save_replay_snapshot,
        )

        cfg = self.cfg
        sfx = replay_shard_suffix()
        host_state = self._params_host(state)
        t0 = time.perf_counter()
        if self._ckpt_inc is not None:
            self._ckpt_inc.save(int(np.asarray(host_state.step)))
            if self._proc_idx == 0:
                save_checkpoint(
                    cfg.learner.checkpoint_dir, host_state, replay=None
                )
        else:
            if self._n_proc > 1:
                from ape_x_dqn_tpu.parallel.multihost import barrier

                if self._proc_idx != 0:
                    save_replay_snapshot(
                        cfg.learner.checkpoint_dir,
                        int(np.asarray(host_state.step)),
                        self.comps.replay,
                        replay_suffix=sfx,
                    )
                barrier("replay-shards-before-state-commit")
            if self._proc_idx == 0:
                # Service-attached replay: the shards own their chains —
                # only the train-state leg saves here.
                save_checkpoint(
                    cfg.learner.checkpoint_dir,
                    host_state,
                    replay=(None if self._remote_replay is not None
                            else self.comps.replay),
                    replay_suffix=sfx,
                )
        # Learner-visible checkpoint stall — the number the incremental
        # subsystem exists to shrink (demos/ckpt_stall.json).
        stall_ms = (time.perf_counter() - t0) * 1e3
        self.logger.log("ckpt/learner_stall_ms", stall_ms)
        self.recorder.record(
            "checkpoint", step=self._learner_step,
            stall_ms=round(stall_ms, 1),
        )

    def _save_fused_checkpoint(self) -> str:
        """Periodic fused-mode save.  The HBM snapshot (state_dict) excludes
        staged-but-uningested host rows — drain them into the ring first so
        a crash-restore from THIS checkpoint loses nothing (rows actors
        stage mid-save remain the only, bounded, gap)."""
        from ape_x_dqn_tpu.utils.checkpoint import save_checkpoint

        self.fused.ingest_staged(drain=True)
        t0 = time.perf_counter()
        if self._ckpt_inc is not None:
            # Replay leg: span gathers dispatched here (this is the
            # train()-caller thread, as delta_state_dict requires); the
            # device_get + IO land on the writer thread.
            self._ckpt_inc.save(self.fused.step)
            path = save_checkpoint(
                self.cfg.learner.checkpoint_dir, self.fused.state,
                replay=None,
            )
        else:
            path = save_checkpoint(
                self.cfg.learner.checkpoint_dir, self.fused.state,
                replay=self.fused,
            )
        stall_ms = (time.perf_counter() - t0) * 1e3
        self.logger.log("ckpt/learner_stall_ms", stall_ms)
        self.recorder.record(
            "checkpoint", step=self._learner_step,
            stall_ms=round(stall_ms, 1),
        )
        return path

    def _obs_run_start(self, target: int) -> None:
        """Flight-recorder run header + SIGTERM flush hook (main thread
        only — install_sigterm no-ops elsewhere)."""
        if self._postmortem_dir:
            self.recorder.install_sigterm(self._postmortem_dir)
        self.recorder.record(
            "run_start", target=target,
            mode="fused" if self.fused is not None else "host",
            actor_mode=self.cfg.actor.mode,
        )
        self.health.beat("learner")
        if self.supervisor is not None:
            self.supervisor.start()
        if self._chaos is not None:
            self._chaos.start()
        if self.autopilot is not None:
            self.autopilot_aggregator.start()
            self.autopilot.start()

    def _obs_fault(self, e: BaseException) -> None:
        """Fault path: one recorded event + a post-mortem dump.  Both are
        best-effort by construction — a dump failure must never mask the
        exception that brought us here."""
        self.recorder.record("fault", error=f"{type(e).__name__}: {e}")
        self.recorder.dump(self._postmortem_dir, "fault")

    def _close_obs(self) -> None:
        # Central serving teardown first: the workers are already joined
        # by every caller's finally ordering, so no select is in flight.
        if self._central_net is not None:
            try:
                self._central_net.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if self._central_server is not None:
            try:
                self._central_server.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            # Reference kept: the final emit still reads batch occupancy
            # (closing is idempotent; counters survive close).
        for sel in self._central_selectors:
            try:
                sel.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if self.autopilot is not None:
            try:
                self.autopilot.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if self.autopilot_aggregator is not None:
            try:
                self.autopilot_aggregator.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if self.fleet_registry is not None:
            try:
                self.fleet_registry.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            self.fleet_registry = None
        if self._chaos is not None:
            try:
                self._chaos.stop()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if self.supervisor is not None:
            try:
                self.supervisor.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if self.obs_server is not None:
            try:
                self.obs_server.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            self.obs_server = None
        if self._remote_replay is not None:
            # Stop the probe thread and release the RPC sockets (fd-leak
            # guard discipline).  Soft close: a later op on the client
            # simply reconnects — only background recovery stops.
            try:
                self._remote_replay.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass

    def register_jsonl_section(self, name: str, fn) -> None:
        """Fold ``fn()`` into every periodic emit as section ``name`` —
        how serve.py --attach rides a ``serving_net`` section on the
        trainer's JSONL stream (docs/METRICS.md).  A section that raises
        is dropped from that record, never the record itself."""
        self._jsonl_sections[str(name)] = fn

    def _sections_extra(self) -> dict:
        out = {}
        for name, fn in getattr(self, "_jsonl_sections", {}).items():
            try:
                out[name] = fn()
            except Exception:  # noqa: BLE001 — a sick section must not
                pass           # take the trainer's emit loop down
        return out

    def _obs_extra(self) -> dict:
        """Per-worker shm stats + lineage on the SAME emit as learner
        throughput — the fleet-wide record the ISSUE's analysis needs in
        one place."""
        out: dict = {}
        pool = getattr(self.worker, "pool", None)
        if pool is not None and hasattr(pool, "worker_stats"):
            ws = pool.worker_stats()
            if ws:
                out["workers"] = ws
        if self._lineage is not None and self._lineage.age_hist.count:
            out["lineage"] = self._lineage.summary(include_recent=False)
        return out

    def _transport_extra(self) -> dict:
        """Experience-transport metrics (process-actor mode): ingest
        bytes/s, chunk latency, backpressure, torn-record salvage —
        absent in thread mode (no cross-process transport).  On the tcp
        backend a ``net`` section rides along (docs/METRICS.md): frame/
        reconnect/torn counters plus param fan-out cost per push."""
        pool = getattr(self.worker, "pool", None)
        if pool is None or not hasattr(pool, "transport_stats"):
            return {}
        out = {"xp_transport": pool.transport_stats()}
        net = pool.net_stats() if hasattr(pool, "net_stats") else {}
        if net:
            out["net"] = net
        return out

    def _tier_extra(self) -> dict:
        """Tiered-replay accounting on the JSONL stream (docs/METRICS.md
        ``replay_tier`` section): hot/cold occupancy, spill/fault
        counters, and the fault-latency summary — absent unless the host
        replay runs with a hot frame budget."""
        replay = self.comps.replay
        if replay is None or getattr(replay, "tier", None) is None:
            return {}
        stats = replay.tier_stats()
        return {"replay_tier": stats} if stats else {}

    def _ckpt_extra(self) -> dict:
        """Incremental-checkpoint accounting on the JSONL stream: saves /
        bases / deltas / bytes, learner-visible stall, and inflight_skips
        (cadence backpressure — a save refused because the previous one was
        still being written; the next delta covers the wider span)."""
        if self._ckpt_inc is None:
            return {}
        return {"ckpt": self._ckpt_inc.stats()}

    def _supervisor_extra(self) -> dict:
        """Supervision accounting on the JSONL stream (docs/METRICS.md
        ``supervisor`` section): the four policy counters plus the live
        policy state (per-worker backoff, quarantine list, watchdog
        phase) — absent only when supervisor.enabled=false."""
        if self.supervisor is None:
            return {}
        s = self.supervisor
        return {"supervisor": {
            "respawns": int(s.respawns.value),
            "quarantines": int(s.quarantines.value),
            "degradations": int(s.degradations.value),
            "fallback_restores": int(s.fallback_restores.value),
            "quarantined": sorted(s.respawn_policy.quarantined),
            "watchdog": s.watchdog.phase if s.watchdog is not None else None,
        }}

    def _emit_fused(self, metrics, final: bool = False) -> dict:
        import numpy as np

        # Arena hygiene at the log cadence: the learner thread's staging /
        # snapshot / transfer scratch otherwise grows RSS ~MB/s for the
        # life of the run (measured in the round-5 soak; utils/memory).
        trim_malloc()
        eps = self.worker.drain_episodes()
        for e in eps:
            self.episode_returns.append(e.episode_return)
            self.logger.log("episode/return", e.episode_return)
            self.logger.log("episode/length", e.episode_length)
        if metrics is not None:
            # One host sync per log period, not per call.
            self.logger.log("learner/loss", float(np.asarray(metrics.loss)[-1]))
            self.logger.log("learner/mean_q", float(np.asarray(metrics.mean_q)[-1]))
            if metrics.routing is not None:
                # Expert layers' counters, a step (mean over the call's K).
                self._routing = {k: float(np.mean(np.asarray(v)))
                                 for k, v in metrics.routing.items()}
            if getattr(metrics, "attention", None) is not None:
                self._attention = {k: float(np.mean(np.asarray(v)))
                                   for k, v in metrics.attention.items()}
            if getattr(metrics, "scan", None) is not None:
                self._scan = {k: float(np.mean(np.asarray(v)))
                              for k, v in metrics.scan.items()}
            if getattr(metrics, "delta", None) is not None:
                self._delta = {k: float(np.mean(np.asarray(v)))
                               for k, v in metrics.delta.items()}
        return self.logger.emit(
            step=self._learner_step,
            actor_steps=self.worker.actor_steps,
            replay_size=self.fused.size,
            staged_rows=self.fused.staged_rows,
            steps_per_sec=round(self._steps_rate.rate(), 1),
            actor_fps=round(self._fps.rate(), 1),
            param_version=self.store.version,
            actor_restarts=self.worker.restarts,
            actor_heartbeat_age=round(time.monotonic() - self.worker.heartbeat, 3),
            stage_us=self.timers.us_per_call(),
            **({"routing": self._routing} if self._routing else {}),
            **({"attention": self._attention} if self._attention else {}),
            **({"scan": self._scan} if self._scan else {}),
            **({"delta": self._delta} if self._delta else {}),
            final=final,
            **self._transport_extra(),
            **self._ckpt_extra(),
            **self._supervisor_extra(),
            **self._obs_extra(),
            **self._sections_extra(),
        )

    def _place(self, host_batch):
        """Stage a host batch on device — sharded over the mesh's data axis
        in data-parallel mode — keeping host indices for the deferred
        priority write-back.  Multi-host: this host's rows only, assembled
        into the global batch (parallel.place_local_batch)."""
        import jax

        indices = np.asarray(host_batch.indices)
        if self.mesh is not None:
            if self._n_proc > 1:
                from ape_x_dqn_tpu.parallel.dp import place_local_batch

                return indices, place_local_batch(host_batch, self.mesh)
            from ape_x_dqn_tpu.parallel import place_batch

            return indices, place_batch(host_batch, self.mesh)
        return indices, jax.device_put(host_batch)

    def _params_host(self, tree):
        """Host copy of a replicated pytree (params or the whole train
        state) under multi-host SPMD — device_get/np.asarray on arrays
        spanning non-addressable devices raises, so read each leaf's local
        replica instead.  Single-process: pass through untouched."""
        if self._n_proc == 1:
            return tree
        import jax

        from ape_x_dqn_tpu.parallel.multihost import host_value

        return jax.tree_util.tree_map(
            lambda x: host_value(x) if hasattr(x, "addressable_data") else x,
            tree,
        )

    def _priorities_host(self, priorities) -> np.ndarray:
        """Host numpy of the step's priorities: under multi-host SPMD only
        this host's shard (its own replay rows) — np.asarray on an array
        spanning non-addressable devices raises."""
        if self._n_proc > 1:
            from ape_x_dqn_tpu.parallel.multihost import local_shard

            return local_shard(priorities)
        return np.asarray(priorities)

    def _emit(self, metrics=None, final: bool = False) -> dict:
        trim_malloc()  # arena hygiene at the log cadence (utils/memory)
        eps = self.worker.drain_episodes()
        for e in eps:
            self.episode_returns.append(e.episode_return)
            self.logger.log("episode/return", e.episode_return)
            self.logger.log("episode/length", e.episode_length)
        if metrics is not None:
            self.logger.log("learner/loss", float(metrics.loss))
            self.logger.log("learner/mean_q", float(metrics.mean_q))
        return self.logger.emit(
            step=self._learner_step,
            actor_steps=self.worker.actor_steps,
            replay_size=self.comps.replay.size(),
            steps_per_sec=round(self._steps_rate.rate(), 1),
            actor_fps=round(self._fps.rate(), 1),
            param_version=self.store.version,
            actor_restarts=self.worker.restarts,
            actor_heartbeat_age=round(time.monotonic() - self.worker.heartbeat, 3),
            stage_us=self.timers.us_per_call(),
            final=final,
            **self._transport_extra(),
            **self._tier_extra(),
            **self._ckpt_extra(),
            **self._supervisor_extra(),
            **self._obs_extra(),
            **self._sections_extra(),
        )
