"""Shared component construction: config → (network, state, replay, fleet).

Both runtimes — the deterministic single-process driver and the async
pipeline — wire the same objects; this is the one place config becomes
components (the analogue of reference main.py:28-58's inline wiring, as a
reusable function instead of a ``__main__`` block).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ape_x_dqn_tpu.actors import ActorFleet
from ape_x_dqn_tpu.config import ApexConfig, network_kwargs
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.learner.train_step import init_train_state, make_optimizer
from ape_x_dqn_tpu.models.dueling import build_network
from ape_x_dqn_tpu.replay import PrioritizedReplay
from ape_x_dqn_tpu.types import TrainState
from ape_x_dqn_tpu.utils.profiling import launch_span


@dataclasses.dataclass
class Components:
    cfg: ApexConfig
    obs_shape: tuple
    num_actions: int
    network: object
    optimizer: object
    state: TrainState
    learner_step: int          # host-side mirror (== restored step or 0)
    replay: Optional[PrioritizedReplay]   # None in device-replay mode
    env_fns: List[Callable]
    # Checkpoint dir/path a restore actually came from (None = scratch) —
    # device-replay runtimes load their HBM replay snapshot from it after
    # constructing the fused learner.
    restored_path: Optional[str] = None

    def make_train_step(self):
        """The fused learner step with this config's loss/target-sync knobs —
        one construction point for both runtimes."""
        from ape_x_dqn_tpu.learner.train_step import build_train_step

        return build_train_step(
            self.network,
            self.optimizer,
            loss_kind=self.cfg.learner.loss,
            target_sync_freq=self.cfg.learner.q_target_sync_freq,
        )

    def make_sharded_train_step(self):
        """The fused step jitted over a ``data_parallel``-device mesh
        (parallel/dp.py): params replicated, batches sharded over ``data``,
        gradient all-reduce inserted by XLA over ICI.  Returns
        ``(step_fn, sharded_state, mesh)``; the caller adopts the sharded
        state and places batches with ``parallel.place_batch`` —
        BASELINE.md config 4 as a runtime mode (``learner.data_parallel``).
        """
        import numpy as np

        from ape_x_dqn_tpu.parallel import build_sharded_train_step, make_mesh
        from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch

        cfg = self.cfg
        mesh = make_mesh(num_devices=cfg.learner.data_parallel)
        B = cfg.learner.replay_sample_size
        example = PrioritizedBatch(
            transition=NStepTransition(
                obs=np.zeros((B, *self.obs_shape), np.uint8),
                action=np.zeros((B,), np.int32),
                reward=np.zeros((B,), np.float32),
                discount=np.zeros((B,), np.float32),
                next_obs=np.zeros((B, *self.obs_shape), np.uint8),
            ),
            indices=np.zeros((B,), np.int32),
            is_weights=np.ones((B,), np.float32),
        )
        step_fn, sharded_state = build_sharded_train_step(
            self.network,
            self.optimizer,
            mesh,
            self.state,
            example,
            loss_kind=cfg.learner.loss,
            target_sync_freq=cfg.learner.q_target_sync_freq,
        )
        return step_fn, sharded_state, mesh

    def make_sampler(
        self,
        learner_step_fn: Callable[[], int],
        sample_size: Optional[int] = None,
        rng_salt: int = 0,
    ):
        """Replay sampler with the β-annealed IS schedule; ``learner_step_fn``
        supplies the current step for annealing.  ``sample_size`` overrides
        the config batch (multi-host: each process samples its B/n share);
        ``rng_salt`` decorrelates per-host sampling streams."""
        import numpy as np

        from ape_x_dqn_tpu.runtime.single_process import beta_schedule

        rng = np.random.default_rng(self.cfg.seed + 7 + rng_salt)
        cfg = self.cfg
        size = sample_size or cfg.learner.replay_sample_size

        def sample():
            beta = beta_schedule(
                learner_step_fn(), cfg.learner.total_steps, cfg.replay.is_exponent
            )
            return self.replay.sample(size, beta=beta, rng=rng)

        return sample

    @launch_span("fused_learner")
    def make_fused_learner(self):
        """The device-resident fused learner (HBM replay + K-step scan) —
        the ``learner.device_replay=True`` throughput mode.  With
        ``learner.data_parallel > 1`` the ring shards over a data mesh and
        the scan runs SPMD with the grad all-reduce inside
        (replay/device_dp.py — BASELINE config 4's fused spelling)."""
        cfg = self.cfg
        mesh = None
        if cfg.learner.data_parallel > 1:
            from ape_x_dqn_tpu.parallel import make_mesh

            mesh = make_mesh(num_devices=cfg.learner.data_parallel)
        # The fused scan syncs targets at call boundaries, exact only when
        # freq % K == 0 — round the freq down to a multiple of K (never
        # below K) so the default config (2500, K=128) syncs exactly rather
        # than up to K-1 steps late.
        K = cfg.learner.steps_per_call
        freq = cfg.learner.q_target_sync_freq
        freq = max(K, freq - freq % K)
        kwargs = dict(
            capacity=cfg.replay.capacity,
            batch_size=cfg.learner.replay_sample_size,
            steps_per_call=K,
            ingest_block=cfg.learner.ingest_block,
            priority_exponent=cfg.replay.priority_exponent,
            target_sync_freq=freq,
            loss_kind=cfg.learner.loss,
            sample_ahead=cfg.learner.sample_ahead,
            mesh=mesh,
        )
        if cfg.replay.dedup:
            from ape_x_dqn_tpu.runtime.fused_dedup import FusedDedupLearner

            return FusedDedupLearner(
                self.network, self.optimizer, self.state, self.obs_shape,
                frame_ratio=cfg.replay.frame_ratio, **kwargs,
            )
        from ape_x_dqn_tpu.runtime.fused_learner import FusedDeviceLearner

        return FusedDeviceLearner(
            self.network, self.optimizer, self.state, self.obs_shape,
            **kwargs,
        )

    def make_fleet(self, seed_offset: int = 0) -> ActorFleet:
        """Build a fresh actor fleet (supervisor restarts call this again —
        actors are stateless modulo ε/seed, so recovery is respawn +
        param re-pull, SURVEY §5 failure detection)."""
        cfg = self.cfg
        return ActorFleet(
            self.env_fns,
            self.network,
            n_step=cfg.actor.num_steps,
            gamma=cfg.actor.gamma,
            epsilon=cfg.actor.epsilon,
            epsilon_alpha=cfg.actor.alpha,
            flush_every=cfg.actor.flush_every,
            sync_every=cfg.actor.sync_every,
            seed=cfg.seed + seed_offset,
            emission=cfg.actor.emission,
            emit_dedup=cfg.replay.dedup,
            emit_dedup_groups=dedup_groups(cfg),
        )


def dedup_groups(cfg: ApexConfig) -> int:
    """Independent dedup streams per fleet: the sharded dedup ring routes
    whole sources to shards, so every fleet must present one source per
    shard or ingest would starve (replay/device_dedup_dp.py docstring)."""
    if cfg.replay.dedup and cfg.learner.device_replay:
        return max(1, cfg.learner.data_parallel)
    return 1


def resolve_spill_dir(cfg: ApexConfig) -> str:
    """Where the cold tier's spill file lives.  "auto" follows the
    postmortem-dir policy: a checkpointed run owns its checkpoint dir (and
    incremental bases reference cold spans by offset into the same tree);
    an ad-hoc run gets a per-pid tempdir instead of a stray directory."""
    import os
    import tempfile

    d = cfg.replay.spill_dir
    if d != "auto":
        return d
    if cfg.learner.checkpoint_every:
        return os.path.join(cfg.learner.checkpoint_dir, "replay_spill")
    return os.path.join(
        tempfile.gettempdir(), f"apex-spill-{os.getpid()}"
    )


@launch_span("components")
def build_components(cfg: ApexConfig) -> Components:
    cfg.validate()
    env_kwargs = dict(
        frame_skip=cfg.env.frame_skip,
        frame_stack=cfg.env.frame_stack,
        episodic_life=cfg.env.episodic_life,
        clip_rewards=cfg.env.clip_rewards,
    )
    probe = make_env(cfg.env.name, seed=cfg.seed, **env_kwargs)
    obs_shape = probe.observation_shape
    num_actions = probe.num_actions
    if cfg.env.state_shape is not None:
        want, got = tuple(cfg.env.state_shape), tuple(obs_shape)
        # Accept the reference's CHW spelling ([1, 84, 84], parameters.json:3)
        # for our HWC layout.
        chw_of_got = (got[-1], *got[:-1]) if len(got) == 3 else got
        if want != got and want != chw_of_got:
            raise ValueError(f"config env.state_shape {want} != actual {got}")
    if cfg.env.action_dim is not None and cfg.env.action_dim != num_actions:
        raise ValueError(
            f"config env.action_dim {cfg.env.action_dim} != actual {num_actions}"
        )

    _dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, None: None}
    net_kwargs = network_kwargs(cfg)
    if _dtypes[cfg.learner.param_dtype] is not None:
        net_kwargs["param_dtype"] = _dtypes[cfg.learner.param_dtype]
    network = build_network(cfg.network, num_actions, **net_kwargs)
    optimizer = make_optimizer(
        cfg.learner.optimizer,
        learning_rate=cfg.learner.learning_rate,
        max_grad_norm=cfg.learner.max_grad_norm,
        second_moment_dtype=_dtypes[cfg.learner.second_moment_dtype],
    )
    if cfg.learner.param_dtype == "bfloat16":
        # bf16 params need f32 update accumulation (see with_float32_master).
        from ape_x_dqn_tpu.learner.train_step import with_float32_master

        optimizer = with_float32_master(optimizer)
    state = init_train_state(
        network, optimizer, jax.random.PRNGKey(cfg.seed),
        jnp.zeros((1, *obs_shape), jnp.uint8),
        target_dtype=_dtypes[cfg.learner.target_dtype],
    )
    # Tiered frame store (replay/tiered.py): a positive hot budget caps
    # the host replay's resident frame bytes; least-recently-sampled spans
    # spill to the resolved dir and fault back on sample.
    tier_kwargs = {}
    if cfg.replay.hot_frame_budget_bytes > 0:
        tier_kwargs = dict(
            hot_frame_budget_bytes=cfg.replay.hot_frame_budget_bytes,
            spill_dir=resolve_spill_dir(cfg),
            spill_span_frames=cfg.replay.spill_span_frames,
            spill_watermark_high=cfg.replay.spill_watermark_high,
            spill_watermark_low=cfg.replay.spill_watermark_low,
        )
    if cfg.learner.device_replay:
        # Throughput mode keeps the ring in HBM (make_fused_learner); the
        # host replay would be ~capacity × 2 frames of dead host RAM.
        replay = None
    elif cfg.replay.service_mode == "attach":
        # Replay as a service (replay/service.py): the "replay" is a
        # retrying RPC client over the shard fleet named by the endpoints
        # file — same add/sample/update_priorities surface, but the
        # learner's sample path now SURVIVES a replay process dying
        # (typed degradation + write-back buffering instead of a wedge).
        from ape_x_dqn_tpu.replay.service import ShardedReplayClient

        replay = ShardedReplayClient.from_endpoints_file(
            cfg.replay.service_endpoints,
            codec=cfg.replay.service_codec,
            dedup=cfg.replay.service_dedup,
            # Cross-tier tracing follows the lineage sample rate: a
            # traced chunk's add/sample/write-back RPCs carry its id.
            trace=cfg.obs.trace_sample_rate > 0,
            request_timeout_s=cfg.replay.service_request_timeout_s,
            probe_interval_s=cfg.replay.service_probe_interval_s,
            seed=cfg.seed,
        )
        if replay.capacity != cfg.replay.capacity:
            raise ValueError(
                f"replay.capacity {cfg.replay.capacity} != the service "
                f"fleet's total {replay.capacity} "
                f"({cfg.replay.service_endpoints}) — the slot-index "
                "arithmetic (lineage, priority routing) must agree"
            )
    elif cfg.replay.dedup:
        from ape_x_dqn_tpu.replay import DedupReplay

        replay = DedupReplay(
            cfg.replay.capacity, obs_shape,
            priority_exponent=cfg.replay.priority_exponent,
            frame_ratio=cfg.replay.frame_ratio,
            **tier_kwargs,
        )
    else:
        replay = PrioritizedReplay(
            cfg.replay.capacity, obs_shape,
            priority_exponent=cfg.replay.priority_exponent,
            frame_compression=cfg.replay.frame_compression,
            **tier_kwargs,
        )
    learner_step = 0
    restored_path = None
    if cfg.learner.restore_from:
        # Resume gate mirroring the reference's load_saved_state
        # (learner.py:18-23) — restoring the FULL train state (and the host
        # replay snapshot, when one was saved), with the same missing-file
        # fallback to scratch.  True means "my checkpoint_dir".
        from ape_x_dqn_tpu.utils.checkpoint import restore_checkpoint

        restore_path = (
            cfg.learner.checkpoint_dir
            if cfg.learner.restore_from is True
            else str(cfg.learner.restore_from)
        )
        # Multi-host SPMD: every host restores the (replicated) train state
        # from the shared dir but ONLY its own replay shard — host i saved
        # replay_h<i>.npz (async_pipeline checkpoint sites).
        from ape_x_dqn_tpu.utils.checkpoint import replay_shard_suffix

        suffix = replay_shard_suffix()
        try:
            # Remote (service-attached) replay: the shards own their own
            # chains — only the train-state leg restores here.
            state, learner_step = restore_checkpoint(
                restore_path, state,
                replay=None if getattr(replay, "remote", False) else replay,
                replay_suffix=suffix,
            )
            restored_path = restore_path
            print(f"restored checkpoint at step {learner_step}")
        except FileNotFoundError:
            print(
                f"WARNING: no checkpoint at {restore_path}; starting from scratch"
            )
    env_fns = [
        (lambda i=i: make_env(cfg.env.name, seed=cfg.seed + 1000 + i, **env_kwargs))
        for i in range(cfg.actor.num_actors)
    ]
    return Components(
        cfg=cfg,
        obs_shape=obs_shape,
        num_actions=num_actions,
        network=network,
        optimizer=optimizer,
        state=state,
        learner_step=learner_step,
        replay=replay,
        env_fns=env_fns,
        restored_path=restored_path,
    )
