"""Typed configuration — the reference's parameters.json vocabulary, validated.

The reference's entire config system is one JSON file fetched by string key
with no schema, one dead key, and the learner's total step count hard-coded
outside config (reference parameters.json:1-34, main.py:12-16,29-33,46 —
SURVEY §2 component 9).  Here the same four-section vocabulary
(``env_conf`` / ``Actor`` / ``Learner`` / ``Replay_Memory``) becomes typed
dataclasses with validation; reference-format JSON files load directly, every
key is consumed, and CLI ``--set section.field=value`` overrides layer on
top.

Key-by-key mapping from the reference file (parameters.json):
  env_conf.name/state_shape/action_dim      → EnvConfig (state_shape/action_dim
    become optional: they are *derived* from the constructed env and only
    validated if given — the reference trusts them blindly)
  Actor.num_actors/T/num_steps/epsilon/alpha/gamma → ActorConfig (same names)
  Actor.n_step_transition_batch_size        → ActorConfig.flush_every (steps
    between chunk emissions; the reference counts buffered transitions)
  Actor.Q_network_sync_freq                 → ActorConfig.sync_every
  Learner.q_target_sync_freq/min_replay_mem_size/replay_sample_size
                                            → LearnerConfig (same names)
  Learner.load_saved_state                  → LearnerConfig.restore_from
  Learner.remove_old_xp_freq                → accepted, no-op: the ring
    buffer evicts FIFO implicitly on overwrite (reference replay.py:71-80's
    periodic scan is structural, not semantic)
  Learner T (hard-coded 500000 at main.py:46) → LearnerConfig.total_steps,
    in config where it belonged
  Replay_Memory.soft_capacity               → ReplayConfig.capacity (hard)
  Replay_Memory.priority_exponent           → ReplayConfig.priority_exponent
  Replay_Memory.importance_sampling_exponent → ReplayConfig.is_exponent —
    read by nothing in the reference (README TODO); live here.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional, Sequence

# Network kinds whose torso is a stack of blocks built from ``ApexConfig.torso``
# (the keys of models/dueling.TORSO_KINDS).
TORSO_NETWORKS = ("lfm2_moe", "laguna_moe", "granite_hybrid", "solar_open2", "ling_hybrid",
                  "olmo_hybrid", "kanana_moe", "nemotron_h")
# Those whose observation is a history of single frames (``frame_history``).
HISTORY_NETWORKS = ("laguna_moe", "granite_hybrid", "solar_open2", "ling_hybrid", "olmo_hybrid",
                    "kanana_moe", "nemotron_h")


@dataclasses.dataclass
class EnvConfig:
    name: str = "chain:10"
    state_shape: Optional[Sequence[int]] = None   # validated if given
    action_dim: Optional[int] = None              # validated if given
    frame_skip: int = 4
    frame_stack: int = 1       # reference parity: single frame (SURVEY §2 comp 5)
    episodic_life: bool = True
    clip_rewards: bool = True


@dataclasses.dataclass
class ActorConfig:
    num_actors: int = 5                   # parameters.json:9
    T: int = 50_000                       # per-actor env steps, parameters.json:10
    num_steps: int = 3                    # n-step horizon, parameters.json:11
    epsilon: float = 0.4                  # parameters.json:12
    alpha: float = 7.0                    # ε-ladder exponent, parameters.json:13
    gamma: float = 0.99                   # parameters.json:14
    flush_every: int = 16                 # chunk emission period (steps)
    sync_every: int = 500                 # param poll period, parameters.json:16
    # n-step window emission: "overlapping" = every step starts a window
    # (stride 1, the Ape-X paper's sliding window); "strided" = only
    # n-aligned starts (stride n — reference parity: the reference's buffer
    # advances n steps per emitted transition, reference actor.py:44-70).
    emission: str = "overlapping"
    # Actor placement: "thread" = fleets as threads in the learner process
    # (vector/fake envs); "process" = num_workers CPU-only worker processes,
    # params over shared memory, experience over a bounded queue
    # (runtime/process_actors.py — the reference's mp.Process actor layout,
    # main.py:50-54, rebuilt on the TPU transport stack).
    mode: str = "thread"
    num_workers: int = 2                  # worker processes (mode="process")
    # Unix niceness applied inside each worker process (mode="process").
    # On hosts where workers share cores with the learner process, raising
    # this keeps the learner's dispatch thread scheduled ahead of worker
    # CPU inference (measured on a 1-core VM: nice-0 workers starve the
    # fused learner ~7x below its solo rate).  0 = scheduler default.
    worker_nice: int = 0
    # Experience-transport backend (mode="process"; runtime/transport.py).
    # "shm" (default): one SIGKILL-safe shared-memory ring per worker
    # incarnation — bit-for-bit the pre-refactor path, single-host only.
    # "tcp" (runtime/net.py): the identical CRC-framed APXT records over
    # one nonblocking socket per worker (loopback or cross-host), params
    # fanned out on the same connection as delta-or-full framed messages.
    transport: str = "shm"
    # Listener bind address for the tcp backend.  Local fleets keep the
    # loopback default; a cross-host fleet binds a routable address
    # (workers dial it back from their hosts).
    transport_host: str = "127.0.0.1"
    # Listener port; 0 binds ephemeral (local fleets — the pool exposes
    # the bound port), a fixed port is for cross-host workers that need a
    # dialable address known in advance.
    transport_port: int = 0
    # Hosts the worker fleet spans (planning arithmetic only — see
    # transport_budget()'s per_host breakdown; shm bytes never leave the
    # learner host, socket buffers are counted per host separately).
    # Must be 1 for the shm backend: /dev/shm cannot cross hosts.
    transport_hosts: int = 1
    # Per-connection kernel socket buffer request (tcp backend; SO_SNDBUF
    # worker-side, SO_RCVBUF learner-side).  This is the tcp twin of
    # xp_ring_bytes: the bytes a worker can have in flight before its
    # writes backpressure (full_waits).
    net_conn_buf_bytes: int = 1 << 20
    # --- wire-efficiency layers (tcp backend; runtime/net.py F_XPB) ---
    # Payload codec for coalesced experience batches, negotiated at the
    # connection hello.  "off" (default) keeps the v1 wire bit-identical;
    # "zlib" deflates every batch (level 1, only kept when it shrinks);
    # "auto" compresses only while the writer observes kernel-buffer
    # backpressure (full_waits growing), so loopback/fast links don't pay
    # codec CPU for bytes they don't need.
    net_codec: str = "off"
    # Coalescing budget: the writer packs APXT records into one wire
    # frame per syscall until this many buffered bytes (or the max-wait
    # below) force a flush.  0 disables coalescing — with net_codec also
    # off that is exactly the v1 one-frame-per-record wire.
    net_coalesce_bytes: int = 0
    # Max milliseconds a record may sit in the coalescing buffer before a
    # write flushes it regardless of occupancy (the worker pump also
    # flushes at every quantum boundary).
    net_coalesce_wait_ms: float = 20.0
    # In-window frame dedup: within a coalesced batch, an observation
    # frame already emitted ships once and repeats become offset refs
    # (n-step overlap makes dense chunks ~2x frame-redundant — the wire
    # twin of replay.dedup's frame ring).  Ingest reconstructs
    # bit-identical records; active only when a batch frame is in use
    # (net_coalesce_bytes > 0 or net_codec != "off").
    net_dedup: bool = True
    # Experience-transport knobs (mode="process"; runtime/shm_ring.py).
    # Each worker incarnation gets one SIGKILL-safe shared-memory ring of
    # xp_ring_bytes: it must hold at least one chunk (a chunk is roughly
    # flush_every × actors-per-worker × 2 × frame bytes in the dense wire
    # format; ~half that under replay.dedup) with slack for the learner's
    # drain cadence — too small and workers sit in ring-full backpressure.
    # Sizing is part of the fd/shm budget at fleet scale: 256 workers at
    # the 8 MB default is 2 GB of /dev/shm and ~5 fds per worker
    # (transport_budget() computes it; ProcessActorPool.start() gates on
    # the /dev/shm free-space check).
    xp_ring_bytes: int = 8 << 20
    # Per-poll byte budget of the learner's batched ring sweep: bounds how
    # long one poll can stall the pump thread behind a burst, without
    # starving any single ring (the sweep round-robins).
    xp_drain_budget_bytes: int = 64 << 20
    # Seconds between worker spawns (throttled fleet start): at 256
    # workers an unthrottled start piles every child's jax import onto the
    # host at once.  0 = spawn back-to-back.
    spawn_stagger_s: float = 0.0
    # Remote-worker slots (tcp backend; tools/host_join.py).  The pool
    # reserves this many extra worker ids beyond num_workers — channels
    # pre-registered on the transport, actor slices carved from the SAME
    # global partition — and publishes a join spec so one command on
    # another host attaches that host's workers to this run.  The learner
    # never spawns or supervises them: a dead remote worker is a quiet
    # channel (its host's launcher owns respawn), never a pool fatal.
    remote_workers: int = 0
    # Where the join spec lands (atomic tmp+rename JSON: endpoint specs +
    # the full run config + the per-run token).  Required non-empty when
    # remote_workers > 0; host_join.py reads it.
    remote_join_path: str = ""
    # --- central inference (SEED-style; serving/central.py) ---
    # Where action selection runs.  "local" (default): each worker holds a
    # param snapshot and runs its own jitted policy_step — the Ape-X
    # shape, params fanned out to every actor.  "central": workers hold
    # NO params; each fleet step ships the observation batch as a
    # CRC-framed inference request to the serving tier's micro-batcher
    # (direct to a ServingNetServer or through the ServingRouter) and the
    # reply carries greedy actions + q-rows + param_version.  ε-greedy is
    # applied WORKER-SIDE on the returned argmax from the same global
    # ε-ladder slice the worker would use locally (pinned by test), so
    # the exploration partition is placement-independent either way.
    inference: str = "local"
    # Serving endpoint the workers dial.  Port 0 = auto: the trainer
    # hosts an in-process PolicyServer + ServingNetServer on an ephemeral
    # port and patches the resolved endpoint into the worker config
    # before spawn (the self-contained one-process-tree deployment); a
    # nonzero port names an external ServingNetServer or ServingRouter.
    inference_host: str = "127.0.0.1"
    inference_port: int = 0
    # Per-run serving token (v2 serve hello).  0 = anonymous (the serving
    # port accepts any client); auto mode generates a fresh token per run
    # so a stale worker from another run is rejected at the handshake.
    inference_token: int = 0
    # Outstanding inference requests each worker pipelines per fleet
    # step: the fleet's observation batch splits into this many
    # contiguous row groups, all in flight on one connection at once, so
    # the central micro-batcher sees real concurrency even from one
    # worker (more workers multiply it).
    inference_inflight: int = 4
    # Obs-payload wire economy (the xpb container from PR 10, applied to
    # the obs→inference path): "zlib" deflates each request's obs batch
    # (kept only when smaller; negotiated at the hello), "off" ships raw.
    # In-request frame dedup rides the same container (identical
    # obs rows — common under frame-stacking and early-episode resets —
    # ship once and repeat as refs) when inference_dedup is set.
    inference_codec: str = "off"
    inference_dedup: bool = True
    # Per-select deadline: one fleet step's action selection not answered
    # within this (across reconnects and whole-request retries) is a
    # typed InferenceUnavailable — the worker then either falls back
    # (below) or keeps retrying with the stall counted, never a silent
    # wedge.
    inference_timeout_s: float = 30.0
    # Sustained-outage behavior.  "none" (default): block with a bounded
    # stall counter until the serving tier answers (paramless actors stay
    # paramless).  "local": fall back to cached-params local inference —
    # the worker keeps its param subscription and a compiled policy_step,
    # serving actions from the last adopted snapshot until the central
    # path recovers (config-gated precisely because it reintroduces the
    # param fan-out the central mode exists to remove).
    inference_fallback: str = "none"
    # Floor between a worker's death and its respawn, enforced by
    # ProcessActorPool.supervise() even when no supervisor policy is
    # attached: a worker whose env crashes deterministically at startup
    # must not spin the pool through spawn->crash->spawn at process-fork
    # speed (each cycle is a full jax import plus a ring/stats-block
    # allocation).  The supervisor's exponential backoff layers ON TOP of
    # this floor; 0 restores the old immediate-respawn behavior.
    respawn_min_interval_s: float = 0.25
    # Elastic headroom for the process pool (autopilot/ scale-up).  The
    # global ε-ladder partition is carved over max(num_workers,
    # max_workers) local wids AT CONSTRUCTION, so a worker grown
    # post-start claims a fresh wid whose actor slice was reserved from
    # step zero — growing never reshuffles a running worker's slice.
    # Only num_workers spawn at start; ProcessActorPool.grow() activates
    # the reserved wids on demand.  0 = num_workers (no headroom, the
    # pre-elastic layout bit-for-bit).
    max_workers: int = 0


@dataclasses.dataclass
class LearnerConfig:
    total_steps: int = 500_000            # reference main.py:46 (hard-coded there)
    q_target_sync_freq: int = 2500        # parameters.json:21
    min_replay_mem_size: int = 20_000     # parameters.json:22
    replay_sample_size: int = 32          # parameters.json:23
    restore_from: str | bool = False      # parameters.json:24 load_saved_state
    optimizer: str = "rmsprop"            # "rmsprop" (parity) | "adam"
    learning_rate: float = 0.00025 / 4    # reference learner.py:26
    loss: str = "huber"                   # "huber" | "squared" (parity)
    max_grad_norm: Optional[float] = 40.0
    publish_every: int = 10               # param-store publish period (steps);
    # the reference republishes the full state_dict EVERY step while actors
    # poll every 500 (learner.py:74 vs actor.py:189) — a push-always/
    # pull-rarely mismatch this cap fixes (SURVEY §2 backend entry).
    checkpoint_every: int = 0             # steps; 0 disables
    checkpoint_dir: str = "checkpoints"
    # Incremental async replay checkpointing (utils/checkpoint_inc): the
    # replay leg leaves save_checkpoint's inline np.savez (minutes of
    # learner dead air at a 17.6 GB dedup ring) for dirty-span delta
    # chunks written by a dedicated writer thread — the learner only
    # snapshots cursors + the span written since the last save.  The
    # train-state leg stays on orbax either way.
    checkpoint_incremental: bool = False
    # Deltas per generation before a full base snapshot bounds the chain
    # (restore replays base + up to this many deltas).
    checkpoint_base_every: int = 16
    # zlib-compress chunk payloads (writer-thread CPU for ~2-4x smaller
    # chunks; the learner-visible stall is unchanged either way).
    checkpoint_compress: bool = False
    # Device-resident fused path (replay/device.py): replay lives in HBM and
    # each dispatch runs steps_per_call sample/train/restamp steps — the
    # throughput mode; False = host replay + per-step train (golden path).
    device_replay: bool = False
    # Data-parallel learner over an N-device mesh.  With device_replay=False
    # (parallel/dp.py): batches shard over ``data``, XLA inserts the
    # gradient all-reduce over ICI, priorities gather back per shard —
    # BASELINE.md config 4.  With device_replay=True (replay/device_dp.py):
    # the HBM ring shards per device and the fused K-step scan runs SPMD
    # with the all-reduce inside the scan body — both fast paths combined.
    # Requires replay_sample_size % data_parallel == 0 (and capacity %
    # data_parallel == 0 in the fused mode).
    data_parallel: int = 1
    steps_per_call: int = 128             # K steps fused per dispatch
    # Fused-mode ingest granularity (rows per compiled device add).  Each
    # block is one host->device dispatch, so bigger blocks mean fewer
    # dispatches on the learner thread.  Must divide by data_parallel in
    # the sharded fused mode.
    ingest_block: int = 256
    # HBM-traffic knobs ("bfloat16" | None): reduced-precision RMSProp
    # second moment and target net — see make_optimizer / init_train_state.
    second_moment_dtype: Optional[str] = None
    target_dtype: Optional[str] = None
    # Store network params in bfloat16 with a float32 master copy inside the
    # optimizer state (train_step.with_float32_master) — halves the param
    # HBM read on every forward/backward.  Updates accumulate in float32, so
    # learning quality matches float32 params (chain-MDP test covers it).
    param_dtype: Optional[str] = None
    # Fused-mode sampling cadence: True draws the slots of all K batches of
    # a dispatch in ONE batched inverse-CDF call from call-entry priorities
    # and restamps once after the scan (replay/device.py sample_slots) —
    # drops ~95 µs/step of fixed op overhead at B=32 for up to K steps of
    # priority staleness, the same order the async Ape-X loop already
    # tolerates.  The double store also gathers the K batches' observations
    # in that call (device_replay_sample_many); the dedup ring fetches each
    # batch's rows inside the scan, so its K is not bound by HBM.  False is
    # strict sequential PER (the test oracle).
    sample_ahead: bool = False


@dataclasses.dataclass
class ReplayConfig:
    capacity: int = 100_000               # parameters.json:28 soft_capacity
    priority_exponent: float = 0.6        # parameters.json:29
    is_exponent: float = 0.4              # parameters.json:30 (dead there, live here)
    # zlib-compress stored frames in the HOST replay (the reference's own
    # README TODO, reference README.md:24) — a memory/CPU trade for big
    # buffers; no effect on the HBM device replay (learner.device_replay).
    frame_compression: bool = False
    # Frame-dedup storage (types.DedupChunk): actors ship each frame once
    # and the replay (host DedupReplay or the HBM dedup ring) stores a
    # single frame ring + per-transition refs — ~frame_ratio/2 of the
    # double-store's footprint end to end.  frame_ratio sizes the frame
    # ring per transition slot; it must cover the emission's arrival ratio
    # (≈ (flush_every + n) / flush_every + truncation extras) or the
    # oldest transitions become unsampleable early (gracefully).
    dedup: bool = False
    frame_ratio: float = 1.25
    # Tiered frame store (replay/tiered.py): > 0 caps the frame bytes held
    # in DRAM — least-recently-sampled frame spans spill to a CRC-framed
    # cold file and fault back on sample, while the sum-tree and every
    # transition column stay hot (sampling law untouched).  This is how
    # 10M+ slot replays run on commodity hosts (ROADMAP item 6: the 2M
    # dedup layout already pins 17.6 GB).  0 disables — the replays
    # allocate their dense rings exactly as before, zero cost when off.
    # Host-replay path only (the fused HBM ring is its own tier).
    hot_frame_budget_bytes: int = 0
    # Spill-file directory.  "auto" = <learner.checkpoint_dir>/replay_spill
    # when checkpointing is on (incremental bases then reference cold
    # spans by offset into a dir the run already owns), else a per-pid
    # tempdir.  An explicit path is used as given.
    spill_dir: str = "auto"
    # Frames per spill span (the eviction/fault granule).  0 = auto-size
    # to ~64 KiB payloads — big enough to amortize record framing + CRC,
    # small enough that one sample batch faults MBs, not GBs.
    spill_span_frames: int = 0
    # Eviction hysteresis, as fractions of the hot budget: the background
    # evictor wakes past high x budget and trims to low x budget.
    spill_watermark_high: float = 1.0
    spill_watermark_low: float = 0.9
    # --- replay as a service (replay/service.py) ---
    # "attach" replaces the in-process replay with a retrying RPC client
    # against a sharded replay fleet: sample/add/update-priorities become
    # framed RPCs over the runtime/net.py wire discipline, the learner
    # survives a shard dying (it keeps training on the surviving shards,
    # priority write-backs to the dead one buffer last-write-wins and
    # flush on recovery), and shards own their own checkpoint chains.
    # "off" (default): the replay lives in the learner's address space,
    # exactly as before.
    service_mode: str = "off"
    # Path to the fleet's endpoints file (written atomically by
    # ReplayServiceFleet; re-read by the client when a shard moves after
    # a respawn).  Required non-empty in attach mode.
    service_endpoints: str = ""
    # RPC payload codec — the wire-efficiency layers carried through:
    # add/sample bodies are F_XPB-encoded (in-window frame dedup + zlib,
    # negotiated at the hello exactly like the experience plane).
    # "auto": shard-side sample replies compress ONLY while the shard's
    # reply path observes socket backpressure (blocked sends), so the
    # priced incompressible worst case (zlib CPU for bytes the link
    # didn't need — demos/replay_svc.json) stops being the default tax;
    # client-side bodies ride the same negotiation.
    service_codec: str = "zlib"
    service_dedup: bool = True
    # Per-request deadline: a request not answered within this (across
    # reconnects and whole-request retries) raises the typed
    # ReplayShardUnavailable and the client routes around the shard.
    service_request_timeout_s: float = 10.0
    # Down-shard probe cadence (the client's background recovery loop:
    # re-resolve the endpoint, cheap digest probe, flush buffered
    # priority write-backs on success).
    service_probe_interval_s: float = 0.5
    # Fleet width for the service-side launcher (replay/service.py CLI /
    # tools; the client takes its shard map from the endpoints file).
    service_shards: int = 2
    # Tiered frame store INSIDE each shard: > 0 caps the frame bytes a
    # ReplayShardServer's PrioritizedReplay holds hot (replay/tiered.py
    # spills least-recently-sampled spans under <ckpt_dir>/spill) — the
    # service-side twin of replay.hot_frame_budget_bytes, which stays a
    # learner-LOCAL feature.  0 disables: shards host dense rings.
    service_hot_frame_budget_bytes: int = 0


@dataclasses.dataclass
class ServingConfig:
    """Policy-serving knobs (ape_x_dqn_tpu/serving/ + the serve CLI).

    The training sections above have reference-parity provenance; this one
    is new surface — the inference half the reference never had.
    """

    max_batch: int = 32          # largest bucket one jitted apply serves
    max_wait_ms: float = 5.0     # deadline: oldest request's max queue wait
    queue_capacity: int = 256    # admission-control bound (load-shed beyond)
    reload_poll_s: float = 0.25  # param-source poll cadence (hot reload)
    # Staleness bound on the served params (seconds since the last adopted
    # snapshot).  Past it the server enters DEGRADED mode: submissions shed
    # with the typed ServerOverloaded (stale answers are worse than loud
    # refusals for a policy tier feeding live actors) and the
    # "serving_params" /healthz component goes 503 until a fresh snapshot
    # is adopted.  0 disables — a checkpoint-dir source with a legitimately
    # old final checkpoint should not degrade by default.
    param_stale_s: float = 0.0
    # --- network transport (serving/net_server.py + serving/router.py) ---
    # Bind host/port for the socket request/reply plane (serve --listen)
    # and the replica router.  Port 0 = ephemeral (the bound port is
    # announced as a `serving_listen` JSONL event — what the router and
    # CI gates parse).  Loopback by default: a public front door is a
    # deployment decision, not a config default.
    listen_host: str = "127.0.0.1"
    listen_port: int = 0
    # Fleet width for `serve --replicas` (0 on the CLI = this default).
    replicas: int = 2
    # Length-prefix cap on the request plane: one absurd prefix must not
    # make a replica buffer a GiB before the crc check would catch it
    # (the transport's own sanity bound stays 1 GiB for param frames).
    max_request_bytes: int = 8 << 20
    # Router /healthz probe cadence; a 503/dead replica drains from
    # rotation within one probe (or instantly on a failed connect).
    probe_interval_s: float = 0.5
    # How long the fleet waits for a replica subprocess to announce its
    # ports (jax import + bucket warmup dominate on cold starts).
    replica_spawn_timeout_s: float = 240.0
    # Param-tail fallback (serving/sources.ParamTailWriter): full
    # snapshot every N publishes, page-deltas between.
    param_tail_base_every: int = 16


@dataclasses.dataclass
class ObsConfig:
    """Fleet-wide observability knobs (ape_x_dqn_tpu/obs/).

    Like ServingConfig this is new surface — the reference has no
    observability at all, and the paper's own analysis (priority staleness,
    age-of-experience, throughput balance) presumes exactly this layer.
    """

    # TCP port for the /metrics + /varz + /healthz exporter thread.
    # None disables the HTTP server entirely; 0 binds an ephemeral port
    # (the bound port is exposed as AsyncPipeline.obs_port and printed on
    # the JSONL stream — what CI smoke gates use).
    export_port: Optional[int] = None
    # Probability that an actor chunk is stamped with a lineage trace id
    # (obs/lineage.py): 0 disables tracing, 1.0 traces every chunk (tests).
    # Sampled per CHUNK, not per transition — a chunk is one flush of a
    # whole fleet slice, so even 0.01 yields steady span coverage.
    trace_sample_rate: float = 0.0
    # Flight-recorder depth: most-recent events kept in memory per process
    # (obs/recorder.py) and mirrored into each worker's shm stats block's
    # event ring, so they survive SIGKILL.
    recorder_depth: int = 256
    # /healthz marks a component degraded when its heartbeat is older than
    # this (seconds).
    heartbeat_stale_s: float = 15.0
    # Where post-mortem records land (flight-recorder dumps on fault /
    # SIGTERM; salvaged worker stats blocks after SIGKILL).  "auto" puts
    # them under <learner.checkpoint_dir>/postmortem when checkpointing is
    # enabled (a checkpointed run owns that directory) and disables them
    # otherwise; an explicit path always enables; None disables.
    postmortem_dir: Optional[str] = "auto"
    # /varz?trace=1 on-demand jax.profiler capture (obs/trace.py): trace
    # this many learner steps (a profiler failure is reported as
    # state "error" on the endpoint, never raised into the run).
    trace_steps: int = 512
    # Trace output root; None → a fresh temp dir per capture.
    trace_dir: Optional[str] = None
    # --- fleet observability plane (obs/fleet.py) ---
    # Aggregator scrape cadence: every endpoint (trainer /varz, replay
    # shards' stats RPC, serving replicas' /varz) is polled once per
    # interval; one dead scrape marks that endpoint down with a
    # scrape_failures count, never a sweep crash.
    fleet_scrape_interval_s: float = 1.0
    # Per-scrape timeout (HTTP and the shard stats RPC alike): a wedged
    # endpoint costs the sweep this much, not a hang.
    fleet_scrape_timeout_s: float = 2.0
    # Rollup exporter port for tools that mount the aggregator
    # (tools/obs_top.py --fleet scrapes it; tools/fleet_obs_smoke.py).
    # None = the mounting tool picks; 0 = ephemeral.
    fleet_port: Optional[int] = None
    # --- declarative SLO rules over the rollup (0 = rule off) ---
    # Age-of-experience ceiling: breach while the fleet-merged
    # age-at-sample p95 exceeds this many milliseconds.
    fleet_slo_age_p95_ms: float = 0.0
    # Central-inference round-trip ceiling: breach while the worst
    # trainer's rtt p99 exceeds this (ms).
    fleet_slo_inference_rtt_p99_ms: float = 0.0
    # Serving-latency ceiling: breach while the replica-merged request
    # p99 exceeds this (ms).
    fleet_slo_serving_p99_ms: float = 0.0
    # Serving-throughput floor: breach while summed replica QPS (scrape-
    # to-scrape reply deltas) falls under this.
    fleet_slo_serving_qps_min: float = 0.0
    # Ring-occupancy band, as fractions of actor.xp_ring_bytes: breach
    # while the worst worker's backlog sits above high (drain too slow)
    # or below low (actors starved).  Defaults (0, 1] leave both off.
    fleet_slo_ring_occupancy_low: float = 0.0
    fleet_slo_ring_occupancy_high: float = 1.0
    # Replay add-path backpressure ceiling: breach while the replay
    # fleet's per-shard add QPS (scrape-to-scrape total_added deltas
    # over live shards) exceeds this — the signal the autopilot's
    # replay loop grows shard count on.  0 = rule off.
    fleet_slo_replay_add_qps_high: float = 0.0
    # Endpoint-liveness rule (on by default): breach while any
    # registered endpoint is failing its scrapes.
    fleet_slo_endpoint_alive: bool = True
    # Burn-rate window: a rule transitions on the breaching FRACTION of
    # the trailing window, not a single sample.
    fleet_slo_window_s: float = 30.0
    # ok->breach fires at burn >= this fraction of the window...
    fleet_slo_burn_threshold: float = 0.5
    # ...and breach->ok only at burn <= this (the hysteresis band
    # between them damps flapping around the bound).
    fleet_slo_clear_threshold: float = 0.1
    # Minimum window samples before ANY transition (one bad scrape is
    # not a breach; one good one is not a recovery).
    fleet_slo_min_samples: int = 3
    # --- flight-data recorder (obs/timeline.py) ---
    # Timeline directory: every aggregator sweep appends one compacted
    # delta record to a CRC-framed on-disk ring here, giving the run a
    # durable fleet time-series (windowed queries, SLO-window rebuild on
    # aggregator respawn, obs_top --timeline, tools/obs_diff.py).
    # "auto" puts it under <learner.checkpoint_dir>/timeline when
    # checkpointing is enabled and disables it otherwise (the
    # postmortem_dir discipline); an explicit path always enables; None
    # disables the recorder.
    timeline_dir: Optional[str] = "auto"
    # Total on-disk budget: oldest committed segments are pruned once
    # the ring exceeds this many bytes (bounded by construction).
    timeline_max_bytes: int = 16 << 20
    # Segment rotation size: a segment is fsynced and committed into the
    # manifest (tmp+rename) once it reaches this many bytes.
    timeline_segment_bytes: int = 1 << 20
    # In-memory tail kept for windowed queries on the sweep path,
    # seconds; disk remains the source of truth for older windows.
    timeline_tail_keep_s: float = 600.0


@dataclasses.dataclass
class FleetConfig:
    """Fleet discovery plane (ape_x_dqn_tpu/fleet/registry.py).

    The run-token-scoped membership registry every tier can join over
    the announce wire (``F_FANN``/``F_FREP``): replay shards, serving
    replicas and remote worker hosts register themselves instead of the
    driver plumbing ports through files and pipes.  ``discovery``
    selects which seam the replay client/aggregator trust; the endpoints
    file stays available as the compat fallback.
    """

    # "registry": membership (the announce channel) drives replay-client
    # and aggregator routing; the endpoints file is only a bootstrap/
    # fallback.  "endpoints": the pre-discovery behavior, unchanged.
    discovery: str = "endpoints"
    # Where the trainer hosts the registry.  Port 0 = ephemeral (the
    # bound port is what fleets/tools hand their members).
    registry_host: str = "127.0.0.1"
    registry_port: int = 0
    # Member announce cadence; the registry's lease sweep expires a
    # member not heard from within ttl_s (member_lost, reason ttl).
    heartbeat_s: float = 1.0
    ttl_s: float = 5.0

    def validate_section(self) -> list:
        return [
            (self.discovery in ("registry", "endpoints"),
             f"unknown fleet.discovery: {self.discovery}"),
            (0 <= self.registry_port <= 65535,
             "fleet.registry_port must be in [0, 65535]"),
            (self.heartbeat_s > 0.0, "fleet.heartbeat_s must be > 0"),
            (self.ttl_s >= self.heartbeat_s,
             "fleet.ttl_s must be >= fleet.heartbeat_s (a member must "
             "get at least one beat per lease)"),
        ]


@dataclasses.dataclass
class SupervisorConfig:
    """Fleet supervision policies (runtime/supervisor.py).

    The repo's recovery machinery — SIGKILL-safe rings with salvage, the
    incremental checkpoint chain, per-component heartbeats — emits signals;
    this section parameterizes the POLICY layer that consumes them: typed
    respawn/backoff/quarantine for workers, a learner-progress watchdog
    with a degrade-before-wedge ladder, and serving staleness shedding
    (serving.param_stale_s).  Default on: supervision is the contract every
    scale direction assumes, and with a healthy fleet it costs one idle
    thread.
    """

    enabled: bool = True
    # Worker respawn: exponential backoff (base doubling per death in the
    # crash-loop window, capped) with multiplicative jitter so a
    # correlated fleet-wide kill doesn't respawn in lockstep.
    respawn_backoff_base_s: float = 0.5
    respawn_backoff_max_s: float = 30.0
    respawn_jitter: float = 0.25          # +/- fraction of the backoff
    # Crash-loop budget: more than this many deaths inside the sliding
    # window quarantines the worker — the fleet shrinks gracefully instead
    # of hot-looping spawns against a deterministic crash.
    crash_loop_window_s: float = 120.0
    crash_loop_budget: int = 5
    # Learner watchdog: no observable progress (the learner step) for
    # stall_deadline_s raises a degraded event; still no progress
    # wedge_deadline_s later declares the run wedged (structured event +
    # /healthz 503) — the operator signal, not an automatic kill.
    stall_deadline_s: float = 120.0
    wedge_deadline_s: float = 120.0
    poll_s: float = 0.5                   # supervisor thread cadence

    def validate_section(self) -> list:
        return [
            (self.respawn_backoff_base_s >= 0.0,
             "supervisor.respawn_backoff_base_s must be >= 0"),
            (self.respawn_backoff_max_s >= self.respawn_backoff_base_s,
             "supervisor.respawn_backoff_max_s must be >= base"),
            (0.0 <= self.respawn_jitter <= 1.0,
             "supervisor.respawn_jitter must be in [0, 1]"),
            (self.crash_loop_window_s > 0.0,
             "supervisor.crash_loop_window_s must be > 0"),
            (self.crash_loop_budget >= 1,
             "supervisor.crash_loop_budget must be >= 1"),
            (self.stall_deadline_s > 0.0,
             "supervisor.stall_deadline_s must be > 0"),
            (self.wedge_deadline_s > 0.0,
             "supervisor.wedge_deadline_s must be > 0"),
            (self.poll_s > 0.0, "supervisor.poll_s must be > 0"),
        ]


@dataclasses.dataclass
class AutopilotConfig:
    """Elastic capacity controller (ape_x_dqn_tpu/autopilot/).  Default OFF.

    The actuation half of ROADMAP item 3: one controller, two loops —
    (a) actor fleet: grow/retire worker processes (and tune the drain
    budget / pipeline depth) to hold age-of-experience p95 under its
    bound and ring occupancy in band; (b) serving fleet: grow/retire
    replicas against the QPS-floor / p99 SLOs.  Decisions consume the
    SLO engine's damped ``slo_breach``/``slo_clear`` events
    (``obs.fleet_slo_*``) plus the fleet rollup, and every one passes
    the shared guardrails (min/max bounds, per-direction cooldowns, a
    hold window against the opposite direction, one step at a time), so
    a flapping signal can never oscillate capacity.
    """

    enabled: bool = False
    # Log every decision as an ``autopilot_action`` event WITHOUT
    # actuating — the rehearsal mode for tuning bounds against a live
    # fleet before handing it the keys.
    dry_run: bool = False
    # Decision cadence (the controller's own thread).
    poll_s: float = 1.0
    # Actor-fleet floor; the ceiling is the pool's reserved capacity
    # (max(actor.num_workers, actor.max_workers)).
    actor_min_workers: int = 1
    # Serving-fleet bounds (replica count the controller may move
    # between; scale-down drains from rotation first, then SIGTERM).
    serving_min_replicas: int = 1
    serving_max_replicas: int = 4
    # Per-direction cooldowns: after a scale action, the SAME direction
    # waits this long before acting again (a booting replica/worker must
    # get a chance to move the metric before the next step).
    cooldown_up_s: float = 10.0
    cooldown_down_s: float = 60.0
    # Flap damper on top of the SLO engine's burn-window hysteresis:
    # after ANY action, the OPPOSITE direction additionally waits this
    # long — an up-down-up oscillation needs at least this period.
    hold_opposite_s: float = 30.0
    # Idle scale-down rule for the serving loop: replicas step down
    # (toward the floor) only while the fleet's per-replica QPS has sat
    # under this bound for the idle burn window AND every governing SLO
    # is green.  0 disables — replicas then only ever scale up.
    serving_idle_qps_per_replica: float = 0.0
    # Burn window for the idle (scale-down) rules — evaluated on the
    # controller's own SloEngine, so scale-down inherits the same
    # damping discipline as the breach-driven scale-up.
    idle_window_s: float = 30.0
    # Drain-budget tuning ladder (actor loop, ring-occupancy-high
    # breach): the pool's per-poll drain budget is doubled per action up
    # to this multiple of its configured value BEFORE any worker is
    # retired — drain harder first, shrink the fleet last.
    drain_tune_max_factor: float = 4.0
    # --- replay fleet (the third autopilot loop; needs fleet.discovery
    # --- =registry so membership, not the endpoints file, carries the
    # --- resharded shard map to clients) ---
    # Shard-count bounds the controller may move the replay fleet
    # between (ReplayServiceFleet.grow / retire — retire is a digest-
    # proven slot-range handoff into the survivors, never a data drop).
    replay_min_shards: int = 1
    replay_max_shards: int = 4
    # Idle scale-down rule for the replay loop: shards step down (toward
    # the floor) only while the fleet's per-shard add QPS has sat under
    # this bound for the idle burn window AND every governing SLO is
    # green.  0 disables — the replay fleet then only ever scales up.
    replay_idle_add_qps_per_shard: float = 0.0

    def validate_section(self) -> list:
        return [
            (self.poll_s > 0.0, "autopilot.poll_s must be > 0"),
            (self.actor_min_workers >= 1,
             "autopilot.actor_min_workers must be >= 1"),
            (self.serving_min_replicas >= 1,
             "autopilot.serving_min_replicas must be >= 1"),
            (self.serving_max_replicas >= self.serving_min_replicas,
             "autopilot.serving_max_replicas must be >= "
             "autopilot.serving_min_replicas"),
            (self.cooldown_up_s >= 0.0,
             "autopilot.cooldown_up_s must be >= 0"),
            (self.cooldown_down_s >= 0.0,
             "autopilot.cooldown_down_s must be >= 0"),
            (self.hold_opposite_s >= 0.0,
             "autopilot.hold_opposite_s must be >= 0"),
            (self.serving_idle_qps_per_replica >= 0.0,
             "autopilot.serving_idle_qps_per_replica must be >= 0"),
            (self.idle_window_s > 0.0,
             "autopilot.idle_window_s must be > 0"),
            (self.drain_tune_max_factor >= 1.0,
             "autopilot.drain_tune_max_factor must be >= 1"),
            (self.replay_min_shards >= 1,
             "autopilot.replay_min_shards must be >= 1"),
            (self.replay_max_shards >= self.replay_min_shards,
             "autopilot.replay_max_shards must be >= "
             "autopilot.replay_min_shards"),
            (self.replay_idle_add_qps_per_shard >= 0.0,
             "autopilot.replay_idle_add_qps_per_shard must be >= 0"),
        ]


@dataclasses.dataclass
class ChaosConfig:
    """Deterministic fault injection (obs/chaos.py).  Default OFF.

    Every knob is an injection cadence (mean seconds between events of
    that kind; 0 disables the kind) driven by one seeded schedule, so a
    chaos run is REPRODUCIBLE: same seed, same fault sequence.  The chaos
    monkey only ever attacks the run it is attached to — worker processes
    of its own pool, chunk files of its own checkpoint dir.
    """

    enabled: bool = False
    seed: int = 0
    kill_interval_s: float = 0.0          # SIGKILL a random live worker
    sigstop_interval_s: float = 0.0       # SIGSTOP + later SIGCONT
    sigstop_hold_s: float = 0.5
    # SIGKILL a worker AND scribble an uncommitted torn record into its
    # ring before salvage — the deterministic "killed mid-write" shape.
    torn_record_interval_s: float = 0.0
    # Flip one byte in a committed APXC chunk file (the restore-fallback
    # path's trigger; takes effect at the next restore, not mid-run).
    corrupt_chunk_interval_s: float = 0.0
    # Transient /dev/shm pressure: allocate shm_fill_bytes for hold_s.
    shm_fill_interval_s: float = 0.0
    shm_fill_bytes: int = 64 << 20
    shm_fill_hold_s: float = 1.0
    # Per-env-step latency injected inside worker processes (mean ms,
    # seeded jitter) — the slow-env scenario.
    env_latency_ms: float = 0.0
    # Per-batch service latency injected inside the serving tier's apply
    # path (mean ms, seeded +/-25% jitter; serving/server.PolicyServer).
    # The serving twin of env_latency_ms: it makes replica service time
    # SLEEP-bound, so a 1-core CI host can exercise real capacity
    # scaling (replicas sleeping concurrently genuinely multiply
    # throughput) — the disturbance the autopilot smoke drives its
    # serving loop with.
    serving_delay_ms: float = 0.0
    # --- RPC-plane chaos (replay/service.py shards) ---
    # Mean per-request service delay (ms, seeded +/-50% jitter) injected
    # shard-side before the request executes — the slow-replay scenario
    # the client's deadline/backoff discipline exists for.
    rpc_delay_ms: float = 0.0
    # Probability a well-framed request is silently dropped shard-side
    # (no reply — the lost-reply shape that forces the client's
    # whole-request retry and the at-most-once add dedup).  Seeded.
    rpc_drop_rate: float = 0.0
    # SIGKILL one fleet shard (seeded choice) when the driver's step
    # counter first crosses this value — the deterministic mid-run
    # shard-death drill (ReplayServiceFleet.maybe_kill_at_step).  0 off.
    kill_shard_at_step: int = 0
    # Scheduled shard kills on the chaos monkey's seeded timeline
    # (attach(replay_fleet=...)); 0 disables the kind.
    kill_shard_interval_s: float = 0.0

    def validate_section(self) -> list:
        nonneg = [
            ("kill_interval_s", self.kill_interval_s),
            ("sigstop_interval_s", self.sigstop_interval_s),
            ("sigstop_hold_s", self.sigstop_hold_s),
            ("torn_record_interval_s", self.torn_record_interval_s),
            ("corrupt_chunk_interval_s", self.corrupt_chunk_interval_s),
            ("shm_fill_interval_s", self.shm_fill_interval_s),
            ("shm_fill_hold_s", self.shm_fill_hold_s),
            ("env_latency_ms", self.env_latency_ms),
        ]
        nonneg += [
            ("rpc_delay_ms", self.rpc_delay_ms),
            ("kill_shard_interval_s", self.kill_shard_interval_s),
            ("serving_delay_ms", self.serving_delay_ms),
        ]
        return [
            (v >= 0.0, f"chaos.{k} must be >= 0") for k, v in nonneg
        ] + [
            (self.shm_fill_bytes >= 0, "chaos.shm_fill_bytes must be >= 0"),
            (0.0 <= self.rpc_drop_rate <= 1.0,
             "chaos.rpc_drop_rate must be in [0, 1]"),
            (self.kill_shard_at_step >= 0,
             "chaos.kill_shard_at_step must be >= 0"),
        ]


@dataclasses.dataclass
class ApexConfig:
    env: EnvConfig = dataclasses.field(default_factory=EnvConfig)
    actor: ActorConfig = dataclasses.field(default_factory=ActorConfig)
    learner: LearnerConfig = dataclasses.field(default_factory=LearnerConfig)
    replay: ReplayConfig = dataclasses.field(default_factory=ReplayConfig)
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    fleet: FleetConfig = dataclasses.field(default_factory=FleetConfig)
    supervisor: SupervisorConfig = dataclasses.field(
        default_factory=SupervisorConfig
    )
    autopilot: AutopilotConfig = dataclasses.field(
        default_factory=AutopilotConfig
    )
    chaos: ChaosConfig = dataclasses.field(default_factory=ChaosConfig)
    network: str = "conv"   # "conv" | "nature" | "mlp" | one of TORSO_NETWORKS
    # network=<one of TORSO_NETWORKS>: the torso's block under
    # the published config.json's keys plus the cut (spec_from_config of
    # models/<network>.py); optionally the
    # stem's ``channels`` and the head's ``hidden``.
    torso: dict = dataclasses.field(default_factory=dict)
    seed: int = 0

    def validate(self) -> "ApexConfig":
        a, l, r, s = self.actor, self.learner, self.replay, self.serving
        o = self.obs
        checks = [
            (o.export_port is None or 0 <= o.export_port <= 65535,
             "obs.export_port must be None or in [0, 65535]"),
            (0.0 <= o.trace_sample_rate <= 1.0,
             "obs.trace_sample_rate must be in [0, 1]"),
            (o.recorder_depth >= 1, "obs.recorder_depth must be >= 1"),
            (o.heartbeat_stale_s > 0.0,
             "obs.heartbeat_stale_s must be > 0"),
            (o.trace_steps >= 1, "obs.trace_steps must be >= 1"),
            (o.fleet_scrape_interval_s > 0.0,
             "obs.fleet_scrape_interval_s must be > 0"),
            (o.fleet_scrape_timeout_s > 0.0,
             "obs.fleet_scrape_timeout_s must be > 0"),
            (o.fleet_port is None or 0 <= o.fleet_port <= 65535,
             "obs.fleet_port must be None or in [0, 65535]"),
            (o.fleet_slo_age_p95_ms >= 0.0,
             "obs.fleet_slo_age_p95_ms must be >= 0"),
            (o.fleet_slo_inference_rtt_p99_ms >= 0.0,
             "obs.fleet_slo_inference_rtt_p99_ms must be >= 0"),
            (o.fleet_slo_serving_p99_ms >= 0.0,
             "obs.fleet_slo_serving_p99_ms must be >= 0"),
            (o.fleet_slo_serving_qps_min >= 0.0,
             "obs.fleet_slo_serving_qps_min must be >= 0"),
            (0.0 <= o.fleet_slo_ring_occupancy_low
             <= o.fleet_slo_ring_occupancy_high <= 1.0,
             "obs.fleet_slo_ring_occupancy band must satisfy "
             "0 <= low <= high <= 1"),
            (o.fleet_slo_window_s > 0.0,
             "obs.fleet_slo_window_s must be > 0"),
            (0.0 <= o.fleet_slo_clear_threshold
             <= o.fleet_slo_burn_threshold <= 1.0,
             "obs.fleet_slo thresholds must satisfy "
             "0 <= clear <= burn <= 1"),
            (o.fleet_slo_min_samples >= 1,
             "obs.fleet_slo_min_samples must be >= 1"),
            (o.timeline_segment_bytes >= 1 << 12,
             "obs.timeline_segment_bytes must be >= 4 KiB (a segment "
             "must hold at least a few records before rotating)"),
            (o.timeline_max_bytes >= o.timeline_segment_bytes,
             "obs.timeline_max_bytes must be >= obs.timeline_segment_bytes"),
            (o.timeline_tail_keep_s > 0.0,
             "obs.timeline_tail_keep_s must be > 0"),
            (s.max_batch >= 1, "serving.max_batch must be >= 1"),
            (s.max_wait_ms >= 0.0, "serving.max_wait_ms must be >= 0"),
            (s.queue_capacity >= s.max_batch,
             "serving.queue_capacity must be >= serving.max_batch (a full "
             "batch must be admissible)"),
            (s.reload_poll_s > 0.0, "serving.reload_poll_s must be > 0"),
            (a.num_actors >= 1, "actor.num_actors must be >= 1"),
            (a.num_steps >= 1, "actor.num_steps must be >= 1"),
            (0.0 <= a.epsilon <= 1.0, "actor.epsilon must be in [0, 1]"),
            (0.0 < a.gamma <= 1.0, "actor.gamma must be in (0, 1]"),
            (a.flush_every >= 1, "actor.flush_every must be >= 1"),
            (a.sync_every >= 1, "actor.sync_every must be >= 1"),
            (a.mode in ("thread", "process"),
             f"unknown actor.mode: {a.mode}"),
            (a.emission in ("overlapping", "strided"),
             f"unknown actor.emission: {a.emission}"),
            (a.emission != "strided" or a.flush_every >= a.num_steps,
             "actor.emission=strided requires flush_every >= num_steps"),
            (a.num_workers >= 1, "actor.num_workers must be >= 1"),
            (a.transport in ("shm", "tcp"),
             f"unknown actor.transport: {a.transport}"),
            (0 <= a.transport_port <= 65535,
             "actor.transport_port must be in [0, 65535]"),
            (a.transport_hosts >= 1,
             "actor.transport_hosts must be >= 1"),
            (a.transport == "tcp" or a.transport_hosts == 1,
             "actor.transport_hosts > 1 requires actor.transport=tcp "
             "(shm rings cannot leave the host)"),
            (a.net_conn_buf_bytes >= 1 << 16,
             "actor.net_conn_buf_bytes must be >= 64 KiB (one chunk must "
             "fit the in-flight window)"),
            (a.net_codec in ("off", "zlib", "auto"),
             f"unknown actor.net_codec: {a.net_codec}"),
            (a.net_coalesce_bytes == 0 or a.net_coalesce_bytes >= 1 << 12,
             "actor.net_coalesce_bytes must be 0 (off) or >= 4 KiB (a "
             "budget below one record degenerates to per-record flushes)"),
            (a.net_coalesce_wait_ms >= 0.0,
             "actor.net_coalesce_wait_ms must be >= 0"),
            (a.transport == "tcp"
             or (a.net_codec == "off" and a.net_coalesce_bytes == 0),
             "actor.net_codec / net_coalesce_bytes require "
             "actor.transport=tcp (the shm ring is already zero-copy on "
             "one host — there are no wire bytes to save)"),
            (0 <= a.worker_nice <= 19,
             "actor.worker_nice must be in [0, 19]"),
            (a.xp_ring_bytes >= 1 << 16,
             "actor.xp_ring_bytes must be >= 64 KiB (one chunk + record "
             "framing must fit the ring)"),
            (a.xp_drain_budget_bytes >= 1 << 16,
             "actor.xp_drain_budget_bytes must be >= 64 KiB (the sweep "
             "must be able to drain at least one chunk per poll)"),
            (a.spawn_stagger_s >= 0.0,
             "actor.spawn_stagger_s must be >= 0"),
            (a.respawn_min_interval_s >= 0.0,
             "actor.respawn_min_interval_s must be >= 0"),
            (a.inference in ("local", "central"),
             f"unknown actor.inference: {a.inference}"),
            (0 <= a.inference_port <= 65535,
             "actor.inference_port must be in [0, 65535]"),
            (a.inference_inflight >= 1,
             "actor.inference_inflight must be >= 1"),
            (a.inference_codec in ("off", "zlib"),
             f"unknown actor.inference_codec: {a.inference_codec}"),
            (a.inference_timeout_s > 0.0,
             "actor.inference_timeout_s must be > 0"),
            (a.inference_fallback in ("none", "local"),
             f"unknown actor.inference_fallback: {a.inference_fallback}"),
            (s.param_stale_s >= 0.0,
             "serving.param_stale_s must be >= 0"),
            (0 <= s.listen_port <= 65535,
             "serving.listen_port must be in [0, 65535]"),
            (s.replicas >= 1, "serving.replicas must be >= 1"),
            (s.max_request_bytes >= 1 << 16,
             "serving.max_request_bytes must be >= 64 KiB (one batched "
             "observation must fit a frame)"),
            (s.probe_interval_s > 0.0,
             "serving.probe_interval_s must be > 0"),
            (s.replica_spawn_timeout_s > 0.0,
             "serving.replica_spawn_timeout_s must be > 0"),
            (s.param_tail_base_every >= 1,
             "serving.param_tail_base_every must be >= 1"),
            *self.fleet.validate_section(),
            *self.supervisor.validate_section(),
            *self.autopilot.validate_section(),
            *self.chaos.validate_section(),
            (a.mode != "process" or a.num_actors >= a.num_workers,
             "actor.num_actors must be >= actor.num_workers in process mode"),
            (a.max_workers == 0 or a.max_workers >= a.num_workers,
             "actor.max_workers must be 0 (no headroom) or >= "
             "actor.num_workers (the spawned width is part of the "
             "reserved partition)"),
            (a.max_workers == 0 or a.mode == "process",
             "actor.max_workers requires actor.mode=process (the elastic "
             "pool is the process fleet)"),
            (a.mode != "process"
             or a.num_actors >= max(a.num_workers, a.max_workers),
             "actor.num_actors must cover the reserved worker capacity "
             "(max(num_workers, max_workers)) in process mode"),
            (l.publish_every >= 1, "learner.publish_every must be >= 1"),
            (l.checkpoint_base_every >= 1,
             "learner.checkpoint_base_every must be >= 1"),
            (l.replay_sample_size >= 1, "learner.replay_sample_size must be >= 1"),
            (l.q_target_sync_freq >= 1, "learner.q_target_sync_freq must be >= 1"),
            (r.capacity >= l.replay_sample_size,
             "replay.capacity must be >= learner.replay_sample_size"),
            (l.min_replay_mem_size <= r.capacity,
             "learner.min_replay_mem_size must be <= replay.capacity"),
            (0.0 <= r.priority_exponent <= 1.0,
             "replay.priority_exponent must be in [0, 1]"),
            (not r.dedup or a.flush_every >= a.num_steps,
             "replay.dedup requires actor.flush_every >= actor.num_steps "
             "(DedupChunk carry refs reach at most one chunk back)"),
            (not (r.dedup and r.frame_compression),
             "replay.dedup and replay.frame_compression are mutually "
             "exclusive (the dedup frame ring stores raw uint8)"),
            # Sharded dedup rings route whole sources (per-fleet dedup
            # streams) to shards; every fleet splits into data_parallel
            # groups, so it needs at least that many actors.
            (not (r.dedup and l.device_replay and l.data_parallel > 1)
             or (a.num_actors if a.mode == "thread"
                 else a.num_actors // a.num_workers) >= l.data_parallel,
             "replay.dedup with device_replay needs >= data_parallel "
             "actors per fleet (per worker in process mode) — each fleet "
             "splits into one dedup stream per ring shard"),
            (r.frame_ratio > 0, "replay.frame_ratio must be positive"),
            (r.hot_frame_budget_bytes >= 0,
             "replay.hot_frame_budget_bytes must be >= 0"),
            (not (r.hot_frame_budget_bytes and r.frame_compression),
             "replay.hot_frame_budget_bytes and replay.frame_compression "
             "are mutually exclusive (the cold tier spans raw frame "
             "bytes; compressed slots are per-slot python objects)"),
            (not (r.hot_frame_budget_bytes and l.device_replay),
             "replay.hot_frame_budget_bytes requires device_replay=False "
             "(the tier spills the HOST frame ring; the HBM ring is its "
             "own tier)"),
            (r.spill_span_frames >= 0,
             "replay.spill_span_frames must be >= 0"),
            (0.0 < r.spill_watermark_low <= r.spill_watermark_high <= 1.0,
             "replay spill watermarks must satisfy "
             "0 < low <= high <= 1"),
            (r.service_mode in ("off", "attach"),
             f"unknown replay.service_mode: {r.service_mode}"),
            (r.service_mode == "off" or r.service_endpoints,
             "replay.service_mode=attach requires replay.service_endpoints "
             "(the fleet's endpoints file)"),
            (r.service_codec in ("off", "zlib", "auto"),
             f"unknown replay.service_codec: {r.service_codec}"),
            (r.service_request_timeout_s > 0.0,
             "replay.service_request_timeout_s must be > 0"),
            (r.service_probe_interval_s > 0.0,
             "replay.service_probe_interval_s must be > 0"),
            (r.service_shards >= 1, "replay.service_shards must be >= 1"),
            (r.service_hot_frame_budget_bytes >= 0,
             "replay.service_hot_frame_budget_bytes must be >= 0"),
            (o.fleet_slo_replay_add_qps_high >= 0.0,
             "obs.fleet_slo_replay_add_qps_high must be >= 0"),
            (r.service_mode == "off"
             or not (r.dedup or r.frame_compression
                     or r.hot_frame_budget_bytes or l.device_replay),
             "replay.service_mode=attach hosts a plain PrioritizedReplay "
             "per shard — dedup / frame_compression / hot_frame_budget / "
             "device_replay stay learner-local features"),
            (r.service_mode == "off" or not l.checkpoint_incremental,
             "replay.service_mode=attach is incompatible with "
             "learner.checkpoint_incremental: the shards own the replay's "
             "checkpoint chains (the learner's state leg is unaffected)"),
            (a.remote_workers >= 0,
             "actor.remote_workers must be >= 0"),
            (a.remote_workers == 0
             or (a.mode == "process" and a.transport == "tcp"),
             "actor.remote_workers requires actor.mode=process and "
             "actor.transport=tcp (remote workers dial the experience "
             "listener back)"),
            (a.remote_workers == 0 or a.remote_join_path,
             "actor.remote_workers > 0 requires actor.remote_join_path "
             "(where the join spec for tools/host_join.py lands)"),
            (a.mode != "process"
             or a.num_actors
             >= max(a.num_workers, a.max_workers) + a.remote_workers,
             "actor.num_actors must cover local (incl. max_workers "
             "headroom) + remote workers in process mode"),
            (0.0 <= r.is_exponent <= 1.0, "replay.is_exponent must be in [0, 1]"),
            (self.network in ("conv", "nature", "mlp", *TORSO_NETWORKS),
             f"unknown network kind: {self.network}"),
            ((self.network in TORSO_NETWORKS) == bool(self.torso),
             f"torso holds the block of network={' | '.join(TORSO_NETWORKS)}, "
             "and of no other"),
            (self.network not in HISTORY_NETWORKS or self.env.frame_stack > 1,
             f"network={self.network} reads an observation as a history of single "
             "frames: env.frame_stack must be over 1"),
            (l.optimizer in ("rmsprop", "adam"),
             f"unknown optimizer kind: {l.optimizer}"),
            (l.loss in ("huber", "squared"), f"unknown loss kind: {l.loss}"),
            (l.steps_per_call >= 1, "learner.steps_per_call must be >= 1"),
            (l.ingest_block >= 1, "learner.ingest_block must be >= 1"),
            (not (l.device_replay and l.data_parallel > 1)
             or l.ingest_block % l.data_parallel == 0,
             "learner.ingest_block must be divisible by data_parallel "
             "when device_replay=True"),
            (l.data_parallel >= 1, "learner.data_parallel must be >= 1"),
            (l.replay_sample_size % l.data_parallel == 0,
             "learner.replay_sample_size must be divisible by data_parallel"),
            # Fused + DP (replay/device_dp.py): each device owns an equal
            # ring shard, so capacity must split evenly.
            (not (l.device_replay and l.data_parallel > 1)
             or r.capacity % l.data_parallel == 0,
             "replay.capacity must be divisible by learner.data_parallel "
             "when device_replay=True (per-device HBM ring shards)"),
            (not l.sample_ahead or l.device_replay,
             "learner.sample_ahead=True requires device_replay=True "
             "(it configures the fused HBM-replay scan)"),
            (l.second_moment_dtype in (None, "bfloat16", "float32"),
             f"unknown second_moment_dtype: {l.second_moment_dtype}"),
            (l.target_dtype in (None, "bfloat16", "float32"),
             f"unknown target_dtype: {l.target_dtype}"),
            (l.param_dtype in (None, "bfloat16", "float32"),
             f"unknown param_dtype: {l.param_dtype}"),
            (not (l.second_moment_dtype is not None and l.optimizer == "adam"),
             "second_moment_dtype is only supported for rmsprop"),
        ]
        for ok, msg in checks:
            if not ok:
                raise ValueError(msg)
        return self


_REFERENCE_KEY_MAP = {
    # (reference section, reference key) -> (section attr, field, transform)
    ("env_conf", "name"): ("env", "name", str),
    ("env_conf", "state_shape"): ("env", "state_shape", tuple),
    ("env_conf", "action_dim"): ("env", "action_dim", int),
    ("Actor", "num_actors"): ("actor", "num_actors", int),
    ("Actor", "T"): ("actor", "T", int),
    ("Actor", "num_steps"): ("actor", "num_steps", int),
    ("Actor", "epsilon"): ("actor", "epsilon", float),
    ("Actor", "alpha"): ("actor", "alpha", float),
    ("Actor", "gamma"): ("actor", "gamma", float),
    ("Actor", "n_step_transition_batch_size"): ("actor", "flush_every", int),
    ("Actor", "Q_network_sync_freq"): ("actor", "sync_every", int),
    ("Learner", "T"): ("learner", "total_steps", int),
    ("Learner", "q_target_sync_freq"): ("learner", "q_target_sync_freq", int),
    ("Learner", "min_replay_mem_size"): ("learner", "min_replay_mem_size", int),
    ("Learner", "replay_sample_size"): ("learner", "replay_sample_size", int),
    ("Learner", "load_saved_state"): ("learner", "restore_from", lambda v: v),
    ("Learner", "remove_old_xp_freq"): (None, None, None),  # no-op (ring evicts)
    ("Replay_Memory", "soft_capacity"): ("replay", "capacity", int),
    ("Replay_Memory", "priority_exponent"): ("replay", "priority_exponent", float),
    ("Replay_Memory", "importance_sampling_exponent"): ("replay", "is_exponent", float),
}


def from_reference_json(data: dict) -> ApexConfig:
    """Load a reference-format parameters.json dict.  Unknown keys raise
    (no silently-dead config — SURVEY §5 config subsystem)."""
    cfg = ApexConfig()
    for section, keys in data.items():
        if not isinstance(keys, dict):
            raise ValueError(f"unknown top-level config entry: {section}")
        for key, value in keys.items():
            mapping = _REFERENCE_KEY_MAP.get((section, key))
            if mapping is None:
                raise ValueError(f"unknown config key: {section}.{key}")
            attr, field, transform = mapping
            if attr is None:
                continue  # documented no-op
            setattr(getattr(cfg, attr), field, transform(value))
    return cfg.validate()


# Optional-typed fields where a CLI "none" legitimately means None; anywhere
# else "none" falls through to the typed coercion and raises clearly.
_OPTIONAL_FIELDS = {
    "state_shape", "action_dim", "max_grad_norm",
    "second_moment_dtype", "target_dtype", "param_dtype",
    "export_port", "postmortem_dir", "trace_dir", "fleet_port",
}


def _coerce(current: Any, raw: str, field: str = "") -> Any:
    if raw.lower() in ("none", "null") and field in _OPTIONAL_FIELDS:
        return None
    if current is None:
        # Optional fields carry no type witness when unset — accept numeric
        # spellings as numbers (obs.export_port=8080 must not become a
        # string), anything else as the raw string (paths).
        for conv in (int, float):
            try:
                return conv(raw)
            except ValueError:
                continue
        return raw
    if isinstance(current, bool):
        # bool-defaulted fields may be str|bool unions (learner.restore_from:
        # False or a checkpoint path) — only coerce clearly boolean words,
        # pass anything else through as a string.
        low = raw.lower()
        if low in ("1", "true", "yes"):
            return True
        if low in ("0", "false", "no"):
            return False
        return raw
    if isinstance(current, int):
        return int(raw)
    if isinstance(current, float):
        return float(raw)
    return raw


def apply_overrides(cfg: ApexConfig, overrides: Sequence[str]) -> ApexConfig:
    """Apply CLI ``section.field=value`` overrides (e.g.
    ``actor.num_actors=64``, ``network=mlp``)."""
    for item in overrides:
        if "=" not in item:
            raise ValueError(f"override must be key=value, got: {item}")
        path, raw = item.split("=", 1)
        parts = path.split(".")
        obj = cfg
        for p in parts[:-1]:
            if not hasattr(obj, p):
                raise ValueError(f"unknown config path: {path}")
            obj = getattr(obj, p)
        field = parts[-1]
        if not hasattr(obj, field):
            raise ValueError(f"unknown config field: {path}")
        setattr(obj, field, _coerce(getattr(obj, field), raw, field))
    return cfg.validate()


def load_config(path: Optional[str] = None, overrides: Sequence[str] = ()) -> ApexConfig:
    """Load config: native JSON (sections matching dataclass fields) or
    reference-format parameters.json, then CLI overrides."""
    cfg = ApexConfig()
    if path:
        with open(path) as f:
            data = json.load(f)
        if any(s in data for s in ("env_conf", "Actor", "Learner", "Replay_Memory")):
            cfg = from_reference_json(data)
        else:
            cfg = _from_native_json(data)
    return apply_overrides(cfg, overrides)


def _from_native_json(data: dict) -> ApexConfig:
    cfg = ApexConfig()
    sections = {
        "env": EnvConfig, "actor": ActorConfig,
        "learner": LearnerConfig, "replay": ReplayConfig,
        "serving": ServingConfig, "obs": ObsConfig,
        "supervisor": SupervisorConfig, "autopilot": AutopilotConfig,
        "chaos": ChaosConfig,
    }
    for key, value in data.items():
        if key in sections:
            known = {f.name for f in dataclasses.fields(sections[key])}
            unknown = set(value) - known
            if unknown:
                raise ValueError(f"unknown config keys in {key}: {sorted(unknown)}")
            setattr(cfg, key, sections[key](**value))
        elif key in ("network", "seed", "torso"):
            setattr(cfg, key, data[key])
        elif key.startswith("_"):
            pass  # "_comment" and friends: documentation, not config
        else:
            raise ValueError(f"unknown top-level config entry: {key}")
    return cfg.validate()


def to_dict(cfg: ApexConfig) -> dict:
    return dataclasses.asdict(cfg)


def network_kwargs(cfg: ApexConfig) -> dict:
    """What ``models.dueling.build_network`` takes beside the kind, the
    action count and the dtypes: the torso's block, with the stem's and
    head's widths where it states them."""
    if cfg.network not in TORSO_NETWORKS:
        return {}
    kw = {"torso": {k: v for k, v in cfg.torso.items() if not k.startswith("_")}}
    if "channels" in cfg.torso:
        kw["channels"] = tuple(cfg.torso["channels"])
    if "hidden" in cfg.torso:
        kw["hidden"] = int(cfg.torso["hidden"])
    return kw


def transport_budget(cfg: ApexConfig, num_workers: Optional[int] = None,
                     hosts: Optional[int] = None) -> dict:
    """fd/shm/socket budget of the process-actor experience transport at a
    given fleet scale — the planning arithmetic for "can this host hold
    256 workers" (the live twin is ``ProcessActorPool.shm_accounting``).

    shm backend, per worker the parent holds: one experience-ring shm
    segment (1 fd for the mapping), the control ``mp.Queue`` (a pipe
    pair: 2 fds) plus its feeder-thread wakeup fds, and the process
    sentinel (1 fd) — ~5 fds; the param seqlock buffer is one more
    shared segment for the fleet.  tcp backend: the ring fd becomes a
    connection fd, the ring bytes become kernel socket buffers, and the
    learner host additionally holds one receive buffer per connection
    plus the listener.

    ``per_host`` breaks the budget down across ``hosts`` (default
    ``actor.transport_hosts``): **shm bytes stay local-host-only** —
    rings and the param buffer are learner-host /dev/shm segments and
    are never charged to remote hosts — while socket buffers are counted
    separately per host.  Host 0 is the learner's; workers spread evenly
    (the worker_slice rule).  ``conn_drain_budget_bytes`` is the bounded
    per-connection share of the poll sweep's byte budget, the number
    runtime/transport.make_transport hands each NetChannel.

    Wire-efficiency terms (tcp backend): ``coalesce_buf_bytes`` charges
    one ``net_coalesce_bytes`` staging buffer per worker on its own host
    plus one reassembly window per connection on the learner host;
    ``codec_scratch_bytes`` charges the deflate/inflate scratch (bounded
    by the coalesce budget, floored at 1 MiB for uncoalesced codec-only
    wires) the same way.  Both are 0 with the layers off.
    """
    w = int(num_workers if num_workers is not None else cfg.actor.num_workers)
    kind = cfg.actor.transport
    h_n = int(hosts if hosts is not None else cfg.actor.transport_hosts)
    h_n = max(1, h_n)
    ring = int(cfg.actor.xp_ring_bytes)
    conn = int(cfg.actor.net_conn_buf_bytes)
    conn_drain = max(64 << 10, int(cfg.actor.xp_drain_budget_bytes)
                     // max(1, w))
    coal = int(getattr(cfg.actor, "net_coalesce_bytes", 0))
    codec_on = getattr(cfg.actor, "net_codec", "off") != "off"
    codec_scratch = (max(coal, 1 << 20) if codec_on else 0)
    shm = kind == "shm"
    per_host = []
    for h in range(h_n):
        lo = h * w // h_n
        hi = (h + 1) * w // h_n
        wh = hi - lo
        entry = {
            "host": h,
            "workers": wh,
            # Learner-host /dev/shm only: every ring is a segment shared
            # between the learner and a SAME-HOST worker; remote hosts
            # hold none (and tcp mode allocates no rings at all).
            "shm_bytes": (w * ring if (shm and h == 0) else 0),
            # Kernel socket buffers: each worker's send buffer on its own
            # host; the learner host adds one receive buffer per
            # connection in the fleet.
            "sock_buf_bytes": (
                0 if shm else wh * conn + (w * conn if h == 0 else 0)
            ),
            "conn_drain_budget_bytes": 0 if shm else conn_drain,
            # Wire-efficiency buffers: writer-side coalescing staging on
            # each worker's host; learner host holds a per-connection
            # reassembly window of the same size.
            "coalesce_buf_bytes": (
                0 if shm else wh * coal + (w * coal if h == 0 else 0)
            ),
            "codec_scratch_bytes": (
                0 if shm
                else wh * codec_scratch
                + (w * codec_scratch if h == 0 else 0)
            ),
        }
        per_host.append(entry)
    return {
        "workers": w,
        "transport": kind,
        "hosts": h_n,
        "shm_segments": (w + 1) if shm else 0,  # rings + param buffer
        "ring_bytes_each": ring if shm else 0,
        "ring_bytes_total": w * ring if shm else 0,
        "fds_per_worker": 5,                 # ring/conn fd + queue + sentinel
        "est_parent_fds": 5 * w + 8,         # + param shm / listener, slack
        "per_host": per_host,
    }
