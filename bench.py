"""Benchmark: fused learner throughput on the real chip.

Prints ONE JSON line:
    {"metric": "learner_steps_per_sec", "value": N, "unit": "steps/s",
     "vs_baseline": R, ...extra fields...}

The metric is gradient steps/sec of the device-resident fused pipeline —
ingest → scan_K [prioritized sample → double-Q train step → priority
restamp] in ONE XLA dispatch (replay/device.py:build_fused_learn_step) —
on the flagship dueling conv net at the reference workload scale (batch 32,
84x84x1 uint8 frames, 100k-slot replay: reference parameters.json:3,23,28).

Methodology notes:
  * Every timed call is forced through the serial train-state chain and the
    final loss is pulled to host (``np.asarray`` on a value data-dependent
    on every step), so the clock stops after the device has finished, not
    after the enqueue.
  * K steps are fused per dispatch (lax.scan) and chunks are pre-staged on
    device — overlapping host transfers with device compute is the infeed
    queue's job (runtime/infeed.py), not the learner's.
  * The device sections need a TPU.  Without one, or if one of them fails,
    the run exits non-zero and prints no result line; the host-only
    sections never stand in for them.  The parent initialises jax once and
    owns the chip; every child it starts is CPU-pinned.

``vs_baseline`` is the fraction of the north-star rate prorated per chip:
50_000 steps/s on a v4-8 (4 chips) → 12_500/chip (BASELINE.md).  The chip
here is a v5e (819 GB/s HBM vs v4's 1,228 GB/s); the fused step is HBM-bound
(RMSProp + params traffic), so the proration is conservative by ~1.5x.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

NORTH_STAR_PER_CHIP = 50_000 / 4.0


def _serving_bench(clients: int = 32, duration: float = 6.0,
                   network: str = "conv", max_batch: int = 32,
                   timeout_s: float = 420.0) -> dict:
    """``serving_qps``: tools/loadgen.py in a CPU-pinned subprocess.

    Host-only by construction (the child runs with ``JAX_PLATFORMS=cpu``:
    this parent owns the chip), and the hard timeout keeps a wedged child
    from eating the bench line.
    """
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [
        sys.executable, os.path.join(repo, "tools", "loadgen.py"),
        "--platform", "cpu",
        "--clients", str(clients),
        "--duration", str(duration),
        "--network", network,
        "--max-batch", str(max_batch),
        "--seq-seconds", str(min(3.0, duration)),
        "--low-qps-requests", "10",
    ]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout_s,
        env=env, cwd=repo,
    )
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip()[-400:]
        raise RuntimeError(f"loadgen rc={proc.returncode}: {tail}")
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    return {
        "sequential_qps": r["sequential"]["qps"],
        "batched_qps": r["concurrent"]["qps"],
        "speedup": r["speedup"],
        "clients": r["config"]["clients"],
        "max_batch": r["config"]["max_batch"],
        "network": r["config"]["network"],
        "p50_ms": r["concurrent"]["latency"].get("p50_ms"),
        "p99_ms": r["concurrent"]["latency"].get("p99_ms"),
        "batch_hist": r["concurrent"]["batch_hist"],
        "reloads": r["reloads"]["observed"],
        "checks": r["checks"],
        "note": (
            "CPU-pinned subprocess (host-only); closed-loop clients vs "
            "batch-1 sequential baseline"
        ),
    }


def _serving_net_bench(clients_per_replica: int = 4, duration: float = 6.0,
                       network: str = "mlp", env: str = "random:84x84x1",
                       replica_counts: str = "1,2",
                       timeout_s: float = 560.0) -> dict:
    """``serving_net``: the socket serving tier's scale-out point —
    tools/loadgen.py ``--compare-replicas`` in a CPU-pinned subprocess
    (the ``serving_qps`` isolation pattern: the child runs with
    ``JAX_PLATFORMS=cpu``, a hard timeout keeps a wedged fleet from
    eating the bench line).  One fleet per width at matched per-replica
    offered load, over real sockets through the health-aware router,
    with hot param reloads fanned out as page-deltas mid-window."""
    repo = os.path.dirname(os.path.abspath(__file__))
    env_vars = dict(os.environ)
    env_vars["JAX_PLATFORMS"] = "cpu"
    env_vars["PYTHONPATH"] = repo + os.pathsep + env_vars.get(
        "PYTHONPATH", ""
    )
    cmd = [
        sys.executable, os.path.join(repo, "tools", "loadgen.py"),
        "--platform", "cpu",
        "--compare-replicas", replica_counts,
        "--clients", str(clients_per_replica),
        "--duration", str(duration),
        "--network", network,
        "--env", env,
        "--reloads", "2",
    ]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout_s,
        env=env_vars, cwd=repo,
    )
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip()[-400:]
        raise RuntimeError(f"socket loadgen rc={proc.returncode}: {tail}")
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    runs = {
        k: {
            "qps": v["qps"],
            "p50_ms": v["latency"]["p50_ms"],
            "p99_ms": v["latency"]["p99_ms"],
            "timeouts": v["timeouts"],
            "shed": v["shed"],
            "param_full_bytes": v["param_full_bytes"],
            "delta_bytes_max": v["delta_bytes_max"],
            "param_pushes": v["param"]["param_pushes"],
        }
        for k, v in r["runs"].items()
    }
    return {
        "methodology": r["methodology"],
        "runs": runs,
        "scaleout": r["scaleout"],
        "checks": r["checks"],
        "note": (
            "CPU-pinned subprocess fleet (replica children are separate "
            "processes on this host); matched per-replica closed-loop "
            "load, real sockets through the router, delta param fan-out"
        ),
    }


def _xp_transport_bench(workers=(4, 16, 64), seconds: float = 3.0,
                        rows: int = 64, obs_shape=(84, 84, 1),
                        barrage_rounds: int = 2) -> dict:
    """``xp_transport``: the actor→learner chunk path in isolation — shm
    ring (runtime/shm_ring.py) vs the pre-ring pickle-over-mp.Queue — at
    three fleet widths, plus the SIGKILL barrage proving zero
    fully-committed chunks are lost across random mid-stream kills.

    Host-only by construction (tools/xp_transport.py loads shm_ring.py by
    file path; no process imports jax).
    """
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.xp_transport import run_sigkill_barrage, run_transport_bench

    out = run_transport_bench(list(workers), seconds=seconds, rows=rows,
                              obs_shape=tuple(obs_shape))
    out["sigkill_barrage"] = run_sigkill_barrage(
        workers=min(4, max(workers)), rounds=barrage_rounds, rows=rows,
        obs_shape=tuple(obs_shape),
    )
    for p in out["points"]:
        p["shm_beats_queue_2x"] = bool(p["speedup"] >= 2.0)
    return out


def _xp_net_bench(workers=(4, 16, 64), seconds: float = 3.0,
                  rows: int = 64, obs_shape=(84, 84, 1)) -> dict:
    """``xp_net``: shm ring vs the TCP transport backend on loopback
    (ISSUE 8), now with the wire-efficiency legs alongside (ISSUE 10) —
    plain v1 frames vs coalesce+dedup vs coalesce+dedup+zlib, all
    carrying identical APXT records built from trajectory-shaped chunks
    (matched settings), with wire-vs-logical bytes/transition per leg.
    Loopback is the cross-host transport's upper bound: it pays the
    framing, crc, kernel socket path and per-frame copies, but no wire
    latency.

    Host-only by construction (tools/xp_transport.py loads shm_ring.py
    and net.py by file path; no process imports jax).
    """
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.xp_transport import run_net_bench

    return run_net_bench(list(workers), seconds=seconds, rows=rows,
                         obs_shape=tuple(obs_shape))


def _pipeline_overlap_bench(steps: int = 6400, steps_per_call: int = 64,
                            sync_every: int = 1024,
                            timeout_s: float = 900.0) -> dict:
    """``pipeline_overlap``: the overlapped dispatch pipeline (ISSUE 5)
    swept over depth 1 (strict) / 2 / 4 on one fused workload —
    host-sync counts, steps/s delta, and the device-idle (overlap gap)
    percentiles.

    Runs tools/pipeline_smoke.py --bench in a CPU-pinned subprocess
    (host-only by construction: the child runs with JAX_PLATFORMS=cpu, and
    the hard timeout keeps a wedged child from eating the bench line).
    Sync-count and overlap accounting are platform-independent; what a
    sync costs on the chip is not measured here.
    """
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [
        sys.executable, os.path.join(repo, "tools", "pipeline_smoke.py"),
        "--bench",
        "--steps", str(steps),
        "--steps-per-call", str(steps_per_call),
        "--sync-every", str(sync_every),
    ]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout_s,
        env=env, cwd=repo,
    )
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip()[-400:]
        raise RuntimeError(f"pipeline_smoke rc={proc.returncode}: {tail}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])["pipeline_overlap"]
    out["sync_reduction_10x_at_depth4"] = bool(
        out.get("sync_reduction_x_depth4", 0) >= 10.0
    )
    return out


def _make_chunks(rng, n, m, obs_shape, num_actions):
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.types import NStepTransition

    chunks = []
    for _ in range(n):
        chunks.append(
            jax.device_put(
                NStepTransition(
                    obs=jnp.asarray(
                        rng.integers(0, 255, (m, *obs_shape), dtype=np.uint8)
                    ),
                    action=jnp.asarray(
                        rng.integers(0, num_actions, (m,), dtype=np.int32)
                    ),
                    reward=jnp.asarray(rng.normal(size=(m,)).astype(np.float32)),
                    discount=jnp.full((m,), 0.97, jnp.float32),
                    next_obs=jnp.asarray(
                        rng.integers(0, 255, (m, *obs_shape), dtype=np.uint8)
                    ),
                )
            )
        )
    return chunks


def _validate_samplers(rng) -> dict:
    """Run all three sampler spellings on the real chip at 2M slots and
    report agreement with an exact float64 host oracle."""
    import jax.numpy as jnp

    from ape_x_dqn_tpu.ops.pallas.sampling import (
        _pallas_sample,
        _two_level_sample,
        _xla_sample,
    )

    C, B = 1 << 21, 32
    p_np = rng.random(C, dtype=np.float32) + 1e-3
    p = jnp.asarray(p_np)
    total = float(np.sum(p_np.astype(np.float64)))
    t_np = (rng.random(B) * total).astype(np.float32)
    t = jnp.asarray(t_np)
    cdf64 = np.cumsum(p_np.astype(np.float64))
    exact = np.searchsorted(cdf64, t_np.astype(np.float64), side="right")

    out = {}
    for name, fn in (
        ("two_level", _two_level_sample),
        ("pallas", _pallas_sample),
        ("xla", _xla_sample),
    ):
        idx = np.asarray(fn(p, t))
        # float32 accumulation-order shifts boundaries by a few leaves out
        # of 2M — mass-proportionally immaterial; >64 would be a logic bug.
        # No standalone timing: a single call is dominated by its dispatch,
        # not by a µs-scale kernel.  The sampler's real cost is part of the
        # fused us_per_step headline.
        max_err = int(np.max(np.abs(idx - exact)))
        assert max_err <= 64, f"{name} sampler diverged from f64 oracle: {max_err}"
        out[name] = {"max_leaf_err_2m": max_err}
    return out


def _median_pipeline(trials: int, **kw) -> dict:
    """Run _pipeline_bench ``trials`` times; report the median run (by the
    steady-state window rate) plus per-trial numbers and spread: single
    trials of a host-contended loop vary widely, so claims must come from a
    median with the spread shown."""
    runs = [_pipeline_bench(**kw) for _ in range(trials)]
    key = "window_steps_per_sec"
    vals = sorted(float(r[key]) for r in runs)
    med = vals[len(vals) // 2]
    rep = dict(next(r for r in runs if float(r[key]) == med))
    rep["trials"] = [
        {k: r[k] for k in ("learner_steps_per_sec", "window_steps_per_sec",
                           "actor_fps", "window_actor_fps", "wall_s")}
        for r in runs
    ]
    rep["median_window_steps_per_sec"] = med
    rep["spread_pct"] = round(
        (vals[-1] - vals[0]) / max(med, 1e-9) * 100.0, 1
    )
    return rep


def _pipeline_bench(learner_steps: int = 20_000, steps_per_call: int = 1024,
                    publish_every: int = 4000, num_actors: int = 512,
                    actor_mode: str = "thread", num_workers: int = 4,
                    min_replay: int = 20_000, worker_nice: int = 10,
                    ingest_block: int = 2048, dedup: bool = False) -> dict:
    """End-to-end async pipeline on the real chip: actors + device infeed +
    the fused HBM learner — reports BOTH north-star metrics (learner
    steps/s AND actor FPS) from the same run.

    ``actor_mode="thread"`` puts the actor fleet's batched policy forwards
    on the TPU, CONTENDING with the learner for the one device queue.
    ``actor_mode="process"`` is the designed mitigation: worker processes
    do CPU-only inference (runtime/process_actors.py), the learner owns the
    device alone, and learner steps/s should recover toward the solo
    figure — actor FPS is then bounded by host cores, not the framework."""
    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    cfg = ApexConfig()
    cfg.network = "conv"
    cfg.env.name = "random:84x84x1"
    cfg.actor.num_actors = num_actors   # one fleet: batched policy steps
    cfg.actor.T = 10_000_000
    cfg.actor.flush_every = 16
    cfg.actor.sync_every = 500
    cfg.actor.mode = actor_mode
    cfg.actor.num_workers = num_workers
    # Keep the learner's dispatch thread scheduled ahead of worker CPU
    # inference where they share cores (see actor.worker_nice).
    cfg.actor.worker_nice = worker_nice
    cfg.learner.device_replay = True
    cfg.replay.dedup = dedup
    if actor_mode == "process":
        # Fewer, larger host->device ingest dispatches.
        cfg.learner.ingest_block = ingest_block
    cfg.learner.sample_ahead = True
    cfg.learner.steps_per_call = steps_per_call
    # Publish cadence: each publish is a full param device_get (~13 MB)
    # that also drains the device queue — at the reference's
    # per-step-minded default (10) it would fire once per fused call.
    cfg.learner.publish_every = publish_every
    cfg.learner.min_replay_mem_size = min_replay
    cfg.learner.optimizer = "rmsprop"
    cfg.learner.max_grad_norm = None
    cfg.learner.second_moment_dtype = "bfloat16"
    cfg.learner.target_dtype = "bfloat16"
    cfg.learner.total_steps = learner_steps
    cfg.replay.capacity = 100_000
    devnull = open(os.devnull, "w")
    logger = MetricLogger(stream=devnull)
    pipe = AsyncPipeline(cfg, logger=logger, log_every=1_000_000)
    t0 = time.perf_counter()
    try:
        result = pipe.run(learner_steps=learner_steps, warmup_timeout=300.0)
    finally:
        wall = time.perf_counter() - t0
        devnull.close()
    assert np.isfinite(result["learner/loss"]), result
    return {
        "learner_steps_per_sec": round(result["step"] / wall, 1),
        "actor_fps": round(result["actor_steps"] / wall, 1),
        "learner_steps": result["step"],
        "actor_steps": result["actor_steps"],
        "wall_s": round(wall, 1),
        "window_steps_per_sec": result["steps_per_sec"],
        "window_actor_fps": result["actor_fps"],
        "config": {
            "num_actors": cfg.actor.num_actors,
            "actor_mode": actor_mode,
            "dedup": dedup,
            "num_workers": num_workers if actor_mode == "process" else None,
            "env": cfg.env.name,
            "steps_per_call": cfg.learner.steps_per_call,
            "publish_every": cfg.learner.publish_every,
            "min_replay": min_replay,
            "note": (
                "whole-run averages incl. warmup and compiles; "
                "window_* are the final 30s sliding-window rates "
                "(the steady-state numbers)"
            ),
        },
    }


def _actor_solo_bench(fleet_steps: int = 192, num_actors: int = 512) -> dict:
    """Uncontended actor FPS: one batched fleet stepping RandomFrameEnv with
    jitted policy forwards and the full n-step/priority emission path, no
    learner sharing the device — the actor-side capability ceiling."""
    import jax

    from ape_x_dqn_tpu.actors import ActorFleet, LocalParamSource
    from ape_x_dqn_tpu.envs import RandomFrameEnv
    from ape_x_dqn_tpu.models.dueling import build_network

    net = build_network("conv", 4)
    fleet = ActorFleet(
        [lambda: RandomFrameEnv((84, 84, 1), num_actions=4)] * num_actors,
        net, n_step=3, flush_every=16,
    )
    params = net.init(
        jax.random.PRNGKey(0), np.zeros((1, 84, 84, 1), np.uint8)
    )
    fleet.sync_params(LocalParamSource(params))
    fleet.collect(32)  # compile + warm
    t0 = time.perf_counter()
    chunks, _ = fleet.collect(fleet_steps)
    dt = time.perf_counter() - t0
    emitted = sum(c.transitions.action.shape[0] for c in chunks)
    return {
        "actor_fps": round(fleet_steps * num_actors / dt, 1),
        "fleet_steps_per_sec": round(fleet_steps / dt, 1),
        "num_actors": num_actors,
        "transitions_emitted": emitted,
    }


def _host_replay_bench(capacity: int = 2_000_000, iters: int = 2000) -> dict:
    """Host sum-tree replay throughput at paper scale (SURVEY §7 hard part
    #1: 'the central sum-tree is the only serialized component in Ape-X').
    Measures the learner-facing loop — stratified sample(32) + priority
    restamp — and the actor-facing batched add, on the C++ core."""
    from ape_x_dqn_tpu.replay import PrioritizedReplay
    from ape_x_dqn_tpu.types import NStepTransition

    rng = np.random.default_rng(0)
    obs_shape = (84, 84, 1)
    rep = PrioritizedReplay(capacity, obs_shape)
    M = 4096
    chunk = NStepTransition(
        obs=rng.integers(0, 255, (M, *obs_shape), dtype=np.uint8),
        action=rng.integers(0, 4, (M,), dtype=np.int32),
        reward=rng.normal(size=(M,)).astype(np.float32),
        discount=np.full((M,), 0.97, np.float32),
        next_obs=rng.integers(0, 255, (M, *obs_shape), dtype=np.uint8),
    )
    prio = (np.abs(rng.normal(size=(M,))) + 0.1).astype(np.float32)
    # Occupancy: half the ring (~14 GB of touched frame pages at 2M slots —
    # sized for the 125 GB driver host; shrink --capacity on small VMs).
    n_prefill = max(1, capacity // (2 * M))
    for _ in range(n_prefill):
        rep.add(prio, chunk)
    t0 = time.perf_counter()
    srng = np.random.default_rng(1)
    for _ in range(iters):
        batch = rep.sample(32, rng=srng)
        rep.update_priorities(
            batch.indices, np.abs(rng.normal(size=32)) + 0.1
        )
    dt = time.perf_counter() - t0
    t1 = time.perf_counter()
    for _ in range(16):
        rep.add(prio, chunk)
    dt_add = time.perf_counter() - t1
    # Tree-only (no frame gather): separates the O(log N) structure cost
    # from the host's frame-copy bandwidth, which dominates on weak VMs.
    t2 = time.perf_counter()
    for _ in range(iters):
        idx = rep._tree.sample_stratified(32, srng)
        rep._tree.set(idx, np.abs(rng.normal(size=32)) + 0.1)
    dt_tree = time.perf_counter() - t2
    tree = type(rep._tree).__name__
    return {
        "sample_update_pairs_per_sec": round(iters / dt, 1),
        "samples_per_sec": round(iters * 32 / dt),
        "tree_only_pairs_per_sec": round(iters / dt_tree, 1),
        "add_transitions_per_sec": round(16 * M / dt_add),
        "capacity": capacity,
        "occupancy": min(n_prefill * M, capacity),
        "sum_tree": tree,
        "note": (
            "single-core host VM; frame memcpy dominates the full-path "
            "numbers — tree_only is the sum-tree's own ceiling here"
        ),
    }


def _host_dedup_bench(capacity: int = 2_000_000, iters: int = 2000,
                      n_stripes: int = 1) -> dict:
    """Paper-scale HOST path on the native C++ dedup core: one GIL-released
    call per stage — stratified sample + IS weights
    + both frame gathers fused (rc_sample), ring write + priority set +
    liveness sweep fused (rc_add) — over a THP-backed frame ring storing
    each frame once (2M slots ≈ 17.6 GB at ratio 1.25 vs the double-store's
    28 GB)."""
    from ape_x_dqn_tpu.replay.native_dedup import (
        NativeDedupReplay,
        native_dedup_available,
        native_dedup_error,
    )
    from ape_x_dqn_tpu.types import DedupChunk

    if not native_dedup_available():
        return {"skipped": f"native core unavailable: {native_dedup_error()}"}
    rng = np.random.default_rng(0)
    obs_shape = (84, 84, 1)
    rep = NativeDedupReplay(capacity, obs_shape, frame_ratio=1.25,
                            n_stripes=n_stripes)
    M = 4096  # transitions per chunk over M+1 fresh frames (dedup stream)
    frames = rng.integers(0, 255, (M + 1, *obs_shape), dtype=np.uint8)
    chunk_proto = dict(
        obs_ref=np.arange(M, dtype=np.int32),
        next_ref=np.arange(1, M + 1, dtype=np.int32),
        action=rng.integers(0, 4, M).astype(np.int32),
        reward=rng.normal(size=M).astype(np.float32),
        discount=np.full(M, 0.97, np.float32),
        prev_frames=M + 1,
    )
    prio = (np.abs(rng.normal(size=M)) + 0.1).astype(np.float32)
    n_prefill = max(1, capacity // (2 * M))
    for i in range(n_prefill):
        rep.add(prio, DedupChunk(frames=frames, source=1, chunk_seq=i,
                                 **chunk_proto))
    t0 = time.perf_counter()
    srng = np.random.default_rng(1)
    B = 32 if n_stripes == 1 else 32 - 32 % n_stripes
    for _ in range(iters):
        batch = rep.sample(B, rng=srng)
        rep.update_priorities(
            batch.indices, np.abs(rng.normal(size=B)) + 0.1
        )
    dt = time.perf_counter() - t0
    t1 = time.perf_counter()
    for i in range(16):
        rep.add(prio, DedupChunk(frames=frames, source=1,
                                 chunk_seq=n_prefill + i, **chunk_proto))
    dt_add = time.perf_counter() - t1
    return {
        "sample_update_pairs_per_sec": round(iters / dt, 1),
        "samples_per_sec": round(iters * B / dt),
        "add_transitions_per_sec": round(16 * M / dt_add),
        "capacity": capacity,
        "occupancy": min(n_prefill * M, capacity),
        "n_stripes": n_stripes,
        "frames_gb": round(rep.frames_nbytes() / 1e9, 2),
        "note": (
            "fused C calls (GIL released), THP frame ring, frames stored "
            "once; compare host_replay_2m (python double-store)"
        ),
    }


def _replay_svc_bench(iters: int = 300, batch: int = 32,
                      capacity: int = 16_384, rows: int = 8_192,
                      timeout_s: float = 420.0) -> dict:
    """``replay_svc``: tools/replay_svc_bench.py in a CPU-pinned
    subprocess (the ``serving_qps`` isolation pattern) — RPC sample vs
    in-process sample at the Atari frame shape, with the codec-off /
    codec-zlib / codec-auto split (auto = backpressure-gated reply
    compression: it must price like off on an unloaded loopback, not
    like the always-zlib worst case) and the dedup wire economy on the
    add path (ROADMAP item 1's bench leg; committed:
    demos/replay_svc.json)."""
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "replay_svc_bench.py"),
         "--iters", str(iters), "--batch", str(batch),
         "--capacity", str(capacity), "--rows", str(rows)],
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=repo,
    )
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip()[-400:]
        raise RuntimeError(f"replay_svc_bench rc={proc.returncode}: {tail}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _central_inference_bench(widths: str = "4,16,64",
                             measure_s: float = 20.0,
                             ramp_timeout_s: float = 480.0,
                             skip_kill_leg: bool = False,
                             timeout_s: float = 2400.0) -> dict:
    """``central_inference``: tools/central_inference_bench.py in a
    CPU-pinned subprocess (the ``serving_qps`` isolation pattern, hard
    timeout) — env-steps/s of PARAMLESS workers
    (action selection through the serving tier's micro-batcher, SEED
    style) vs param-holding ones at 4/16/64 worker processes, matched
    config, plus round-trip percentiles, batch occupancy, the obs wire
    economy, and the replica-kill leg (the verify-gate smoke's verdict:
    zero torn / zero drops through a mid-run SIGKILL).  Committed:
    demos/central_inference.json (ROADMAP item 2's bench leg)."""
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [
        sys.executable,
        os.path.join(repo, "tools", "central_inference_bench.py"),
        "--widths", widths, "--measure-s", str(measure_s),
        "--ramp-timeout-s", str(ramp_timeout_s),
    ]
    if skip_kill_leg:
        cmd.append("--skip-kill-leg")
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=repo,
    )
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip()[-400:]
        raise RuntimeError(
            f"central_inference_bench rc={proc.returncode}: {tail}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _replay_tiered_bench(capacity: int = 200_000, iters: int = 1000,
                         hot_frac: float = 0.25,
                         workdir: str | None = None) -> dict:
    """Tiered replay vs in-core (ROADMAP item 6): a dedup replay whose
    frame footprint exceeds the hot budget (hot cap <= 25% of frames)
    sampling/updating at a sustained rate, the background evictor holding
    the budget while the learner-side loop faults what it samples — the
    capacity-beyond-DRAM measurement (committed: demos/replay_tiered.json,
    with the floor arithmetic in demos/README).  Host-only (no jax);
    native core when the toolchain allows, numpy twin otherwise."""
    import shutil
    import tempfile

    from ape_x_dqn_tpu.replay.dedup import DedupReplay
    from ape_x_dqn_tpu.replay.native_dedup import native_dedup_available
    from ape_x_dqn_tpu.replay.tiered import TierEvictor
    from ape_x_dqn_tpu.types import DedupChunk

    if native_dedup_available():
        from ape_x_dqn_tpu.replay.native_dedup import (
            NativeDedupReplay as Replay,
        )
        core = "native"
    else:
        Replay = DedupReplay
        core = "numpy"
    rng = np.random.default_rng(0)
    obs_shape = (84, 84, 1)
    frame_bytes = int(np.prod(obs_shape))
    ring_bytes = int(round(capacity * 1.25)) * frame_bytes
    hot_budget = int(ring_bytes * hot_frac)
    M = 4096
    frames = rng.integers(0, 255, (M + 1, *obs_shape), dtype=np.uint8)
    proto = dict(
        obs_ref=np.arange(M, dtype=np.int32),
        next_ref=np.arange(1, M + 1, dtype=np.int32),
        action=rng.integers(0, 4, M).astype(np.int32),
        reward=rng.normal(size=M).astype(np.float32),
        discount=np.full(M, 0.97, np.float32),
        prev_frames=M + 1,
    )
    prio = (np.abs(rng.normal(size=M)) + 0.1).astype(np.float32)
    n_prefill = max(1, capacity // (2 * M))

    def prefill(rep):
        for i in range(n_prefill):
            rep.add(prio, DedupChunk(frames=frames, source=1, chunk_seq=i,
                                     **proto))

    def run_loop(rep, skew=False):
        # skew=True restamps with lognormal priorities (heavy-tailed TD
        # errors — the realistic PER regime): sampling concentrates, the
        # LRU working set shrinks, fault rate drops.  skew=False is the
        # near-uniform worst case.
        if getattr(rep, "tier", None) is not None:
            # Steady-state methodology: write-back every dirty span's
            # record (keeping residency), then trim to the budget with
            # clean drops — the timed region starts with the hot tier AT
            # its cap and every record current, and measures the steady
            # sample/fault/clean-drop cycle rather than the one-time
            # spill of a cold-started ring.
            rep.tier_flush_dirty()
            while rep.tier_over_watermark():
                rep.spill_cold(max_spans=1024)
        srng = np.random.default_rng(1)
        urng = np.random.default_rng(2)

        def new_prio():
            if skew:
                return np.exp(
                    2.0 * urng.normal(size=32)
                ).astype(np.float32)
            return (np.abs(urng.normal(size=32)) + 0.1).astype(np.float32)

        for _ in range(min(128, iters // 4)):  # warmup (untimed)
            batch = rep.sample(32, rng=srng)
            rep.update_priorities(batch.indices, new_prio())
        t0 = time.perf_counter()
        for _ in range(iters):
            batch = rep.sample(32, rng=srng)
            rep.update_priorities(batch.indices, new_prio())
        return time.perf_counter() - t0

    # In-core baseline (tier off — the zero-cost-when-off configuration).
    rep = Replay(capacity, obs_shape, frame_ratio=1.25)
    prefill(rep)
    dt_incore = run_loop(rep)
    del rep
    # Tiered: hot cap at hot_frac of the ring, background evictor holding
    # it, the sample loop faulting what it draws.
    spill = workdir or tempfile.mkdtemp(prefix="apex-bench-tier-")
    # span_frames=2: obs/next of one transition are adjacent seqs, so a
    # 2-frame span serves both with minimal read amplification (the auto
    # 64 KiB spans fault ~4x more bytes per sampled row at this frame
    # size).
    rep = Replay(capacity, obs_shape, frame_ratio=1.25,
                 hot_frame_budget_bytes=hot_budget, spill_dir=spill,
                 spill_span_frames=2)
    evictor = TierEvictor(rep, poll_s=0.005)
    evictor.start()
    try:
        prefill(rep)
        dt_tiered = run_loop(rep)
        stats = rep.tier_stats()
        # Second point on the SAME warm replay: heavy-tailed priorities
        # (the realistic PER regime) — sampling concentrates, faults drop.
        dt_skew = run_loop(rep, skew=True)
        stats_skew = rep.tier_stats()
    finally:
        evictor.stop()
        del rep
        if workdir is None:
            shutil.rmtree(spill, ignore_errors=True)
    in_core_rate = iters / dt_incore
    tiered_rate = iters / dt_tiered
    skew_rate = iters / dt_skew
    return {
        "tiered_pairs_per_sec_skewed": round(skew_rate, 1),
        "slowdown_x_skewed": round(in_core_rate / max(skew_rate, 1e-9), 2),
        "fault_reads_skewed_phase": (
            stats_skew["fault_reads"] - stats["fault_reads"]
        ),
        "core": core,
        "capacity": capacity,
        "occupancy": min(n_prefill * M, capacity),
        "ring_gb": round(ring_bytes / 1e9, 3),
        "hot_budget_gb": round(hot_budget / 1e9, 3),
        "hot_frac": hot_frac,
        "in_core_pairs_per_sec": round(in_core_rate, 1),
        "tiered_pairs_per_sec": round(tiered_rate, 1),
        "slowdown_x": round(in_core_rate / max(tiered_rate, 1e-9), 2),
        "spill_writes": stats["spill_writes"],
        "spilled_gb": round(stats["spilled_bytes"] / 1e9, 3),
        "fault_reads": stats["fault_reads"],
        "fault_gb": round(stats["fault_bytes"] / 1e9, 3),
        "fault_ms": stats["fault_ms"],
        "hot_bytes_end": stats["hot_bytes"],
        "note": (
            "sample(32)+update pairs; tier holds hot <= "
            f"{int(hot_frac * 100)}% of frames (evictor thread), sample "
            "path faults cold spans through CRC-verified reads; "
            "bit-exactness pinned by tests/test_tiered_replay.py"
        ),
    }


def _checkpoint_stall_bench(capacity: int = 2_000_000,
                            interval_rows: int = 65_536,
                            deltas: int = 3,
                            workdir: str | None = None) -> dict:
    """Learner-visible checkpoint stall: synchronous full-write vs the
    incremental async subsystem (utils/checkpoint_inc), at the 2M-slot host
    DEDUP layout (config3's ~17.6 GB frame ring — the buffer whose inline
    np.savez was minutes of learner dead air).

    Host-only (native C++ dedup core, no jax).  Two measurements:
      * ``full_sync``: one inline full snapshot+write on the caller thread
        — the status-quo save_checkpoint replay leg, same wire format.
      * ``incremental``: async saves at a fixed ingest interval; the
        learner-visible stall is just ``save()`` (dirty-span memcpy +
        enqueue), the write lands on the writer thread.  A half-interval
        delta shows bytes ∝ interval, not capacity.
    """
    import shutil
    import tempfile

    from ape_x_dqn_tpu.replay.native_dedup import (
        NativeDedupReplay,
        native_dedup_available,
        native_dedup_error,
    )
    from ape_x_dqn_tpu.types import DedupChunk
    from ape_x_dqn_tpu.utils.checkpoint_inc import IncrementalCheckpointer

    if not native_dedup_available():
        return {"skipped": f"native core unavailable: {native_dedup_error()}"}
    rng = np.random.default_rng(0)
    obs_shape = (84, 84, 1)
    rep = NativeDedupReplay(capacity, obs_shape, frame_ratio=1.25)
    M = 4096  # transitions per chunk over M+1 fresh frames (dedup stream)
    frames = rng.integers(0, 255, (M + 1, *obs_shape), dtype=np.uint8)
    chunk_proto = dict(
        obs_ref=np.arange(M, dtype=np.int32),
        next_ref=np.arange(1, M + 1, dtype=np.int32),
        action=rng.integers(0, 4, M).astype(np.int32),
        reward=rng.normal(size=M).astype(np.float32),
        discount=np.full(M, 0.97, np.float32),
        prev_frames=M + 1,
    )
    prio = (np.abs(rng.normal(size=M)) + 0.1).astype(np.float32)
    seq = 0

    def ingest(rows: int) -> None:
        nonlocal seq
        for _ in range(max(1, rows // M)):
            rep.add(prio, DedupChunk(frames=frames, source=1, chunk_seq=seq,
                                     **chunk_proto))
            seq += 1

    def churn(iters: int = 32) -> None:
        # Learner-shaped priority restamps between checkpoints — the
        # sparse half of a delta.
        srng = np.random.default_rng(seq)
        for _ in range(iters):
            batch = rep.sample(32, rng=srng)
            rep.update_priorities(
                batch.indices, np.abs(srng.normal(size=32)) + 0.1
            )

    ingest(capacity // 2)  # half occupancy, like host_dedup_2m
    root = tempfile.mkdtemp(prefix="ckpt_stall_", dir=workdir)
    try:
        # -- synchronous full write (the path being replaced) -------------
        full = IncrementalCheckpointer(os.path.join(root, "full"), rep,
                                       sync=True)
        t0 = time.perf_counter()
        full.save(0, force_base=True)
        full_stall_ms = (time.perf_counter() - t0) * 1e3
        full_bytes = full.stats()["last_chunk_bytes"]
        shutil.rmtree(os.path.join(root, "full"))  # reclaim before leg 2

        # -- incremental async -------------------------------------------
        ck = IncrementalCheckpointer(os.path.join(root, "inc"), rep,
                                     base_every=64)
        ck.save(0)        # generation base (async, amortized over the run)
        ck.flush()
        base_bytes = ck.stats()["last_chunk_bytes"]
        stalls, delta_bytes = [], []
        for k in range(deltas):
            ingest(interval_rows)
            churn()
            t0 = time.perf_counter()
            assert ck.save(k + 1)
            stalls.append((time.perf_counter() - t0) * 1e3)
            ck.flush()  # outside the stall: the writer's time, not the
            #             learner's (flush here only so last_chunk_bytes
            #             and the next save's backpressure are exact)
            delta_bytes.append(ck.stats()["last_chunk_bytes"])
        ingest(interval_rows // 2)
        churn()
        t0 = time.perf_counter()
        assert ck.save(deltas + 1)
        half_stall_ms = (time.perf_counter() - t0) * 1e3
        ck.flush()
        half_bytes = ck.stats()["last_chunk_bytes"]
        ck.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    mean_stall = sum(stalls) / len(stalls)
    mean_bytes = sum(delta_bytes) / len(delta_bytes)
    return {
        "capacity": capacity,
        "occupancy": rep.size(),
        "frames_gb": round(rep.frames_nbytes() / 1e9, 2),
        "interval_rows": interval_rows,
        "full_sync": {
            "stall_ms": round(full_stall_ms, 1),
            "bytes": int(full_bytes),
        },
        "incremental": {
            "base_bytes": int(base_bytes),
            "delta_stall_ms": [round(s, 1) for s in stalls],
            "delta_stall_ms_mean": round(mean_stall, 1),
            "delta_bytes": [int(b) for b in delta_bytes],
            "half_interval_stall_ms": round(half_stall_ms, 1),
            "half_interval_bytes": int(half_bytes),
        },
        "stall_reduction_x": round(full_stall_ms / max(mean_stall, 1e-3), 1),
        "delta_vs_full_bytes_x": round(full_bytes / max(mean_bytes, 1), 1),
        "half_over_full_interval_bytes": round(half_bytes / mean_bytes, 3),
        "note": (
            "learner-visible stall = time inside save(); the incremental "
            "save's IO happens on the writer thread.  half_over_full_"
            "interval_bytes ~ 0.5 demonstrates delta bytes proportional "
            "to the checkpoint interval, not the ring capacity"
        ),
    }


def _dedup_fused_bench(args, jnp, jax) -> dict:
    """Single-chip fused learner on the DEDUP HBM ring at the headline
    workload — the per-step cost of the ref indirection vs the
    double-store headline (expected ~neutral: same gathered bytes, half
    the ring HBM)."""
    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step,
        init_train_state,
        make_optimizer,
    )
    from ape_x_dqn_tpu.models.dueling import build_network
    from ape_x_dqn_tpu.replay.device_dedup import (
        build_dedup_fused_learn_step,
        dedup_device_add_frames,
        dedup_device_add_transitions,
        init_dedup_device_replay,
    )

    B, K, C = args.batch_size, args.steps_per_call, args.capacity
    obs_shape, A, M = (84, 84, 1), 4, 256
    target_sync_freq = 2500 - 2500 % K if K <= 2500 else K
    net = build_network("conv", A)
    opt = make_optimizer(
        "rmsprop", max_grad_norm=None, second_moment_dtype=jnp.bfloat16
    )
    step_fn = build_train_step(net, opt, sync_in_step=False, jit=False)
    fused = build_dedup_fused_learn_step(
        step_fn, B, steps_per_call=K, target_sync_freq=target_sync_freq,
        sample_ahead=not args.strict_per,
    )
    replay = init_dedup_device_replay(C, obs_shape, frame_ratio=1.25)
    Q = replay.seq_modulus
    add_f = jax.jit(dedup_device_add_frames, donate_argnums=(0,))
    add_t = jax.jit(dedup_device_add_transitions, donate_argnums=(0,))
    rng = np.random.default_rng(0)
    frames = jax.device_put(jnp.asarray(
        rng.integers(0, 255, (M + 1, *obs_shape), dtype=np.uint8)
    ))
    meta = [
        jax.device_put(jnp.asarray(a)) for a in (
            rng.integers(0, A, (M,)).astype(np.int32),
            rng.normal(size=(M,)).astype(np.float32),
            np.full((M,), 0.97, np.float32),
            np.ones((M,), np.float32),
        )
    ]
    fbase = 0
    for _ in range(40):
        oref = jnp.asarray((fbase + np.arange(M)) % Q, jnp.int32)
        nref = jnp.asarray((fbase + 1 + np.arange(M)) % Q, jnp.int32)
        replay = add_f(replay, frames)
        replay = add_t(replay, oref, nref, *meta)
        fbase += M + 1
    state = init_train_state(
        net, opt, jax.random.PRNGKey(0),
        jnp.zeros((1, *obs_shape), jnp.uint8), target_dtype=jnp.bfloat16,
    )
    key = jax.random.PRNGKey(1)
    for _ in range(2):
        key, sub = jax.random.split(key)
        state, replay, metrics = fused(state, replay, 0.4, sub)
    _ = np.asarray(metrics.loss)
    calls = args.timed_calls
    t0 = time.perf_counter()
    for _ in range(calls):
        key, sub = jax.random.split(key)
        state, replay, metrics = fused(state, replay, 0.4, sub)
    final_loss = np.asarray(metrics.loss)
    dt = time.perf_counter() - t0
    assert np.all(np.isfinite(final_loss)), "non-finite loss in dedup bench"
    rate = calls * K / dt
    return {
        "learner_steps_per_sec": round(rate, 1),
        "us_per_step": round(dt / (calls * K) * 1e6, 1),
        "hbm_frames_mb": round(replay.rows.nbytes / 1e6, 1),
        "double_store_frames_mb": round(
            2 * C * int(np.prod(obs_shape)) / 1e6, 1
        ),
        "config": {"batch_size": B, "steps_per_call": K, "capacity": C,
                   "frame_ratio": 1.25,
                   "sample_ahead": not args.strict_per},
    }


def _fused_headline_bench(args) -> dict:
    """The on-chip headline: fused HBM-replay learner steps/s.  Runs outside
    ``section()``: a failure here fails the bench."""
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.learner.train_step import (
        build_train_step,
        init_train_state,
        make_optimizer,
        with_float32_master,
    )
    from ape_x_dqn_tpu.models.dueling import build_network
    from ape_x_dqn_tpu.replay.device import (
        build_fused_learn_step,
        device_replay_add,
        init_device_replay,
    )

    B, K, C = args.batch_size, args.steps_per_call, args.capacity
    obs_shape, A, M = (84, 84, 1), 4, 256
    target_sync_freq = 2500 - 2500 % K if K <= 2500 else K  # multiple of K

    param_dtype = jnp.bfloat16 if args.param_dtype == "bfloat16" else jnp.float32
    net = build_network("conv", A, param_dtype=param_dtype)
    # Reference-parity RMSProp with the HBM-traffic knobs: no global-norm
    # clip (the reference has none), bfloat16 second moment + target net.
    # Params default to float32.
    opt = make_optimizer(
        "rmsprop", max_grad_norm=None, second_moment_dtype=jnp.bfloat16
    )
    if args.param_dtype == "bfloat16":
        opt = with_float32_master(opt)
    step_fn = build_train_step(net, opt, sync_in_step=False, jit=False)
    fused = build_fused_learn_step(
        step_fn, B, steps_per_call=K, target_sync_freq=target_sync_freq,
        sample_ahead=not args.strict_per,
    )

    rng = np.random.default_rng(0)
    chunks = _make_chunks(rng, 4, M, obs_shape, A)
    prio = jax.device_put(jnp.ones((M,), jnp.float32))

    replay = init_device_replay(C, obs_shape)
    add = jax.jit(device_replay_add, donate_argnums=(0,))
    for i in range(40):  # prefill past min_replay_size
        replay = add(replay, chunks[i % len(chunks)], prio)
    state = init_train_state(
        net,
        opt,
        jax.random.PRNGKey(0),
        jnp.zeros((1, *obs_shape), jnp.uint8),
        target_dtype=jnp.bfloat16,
    )

    key = jax.random.PRNGKey(1)
    for i in range(2):  # compile + steady-state warmup
        key, sub = jax.random.split(key)
        state, replay, metrics = fused(
            state, replay, chunks[i % len(chunks)], prio, 0.4, sub
        )
    _ = np.asarray(metrics.loss)

    calls = args.timed_calls
    t0 = time.perf_counter()
    for i in range(calls):
        key, sub = jax.random.split(key)
        state, replay, metrics = fused(
            state, replay, chunks[i % len(chunks)], prio, 0.4, sub
        )
    final_loss = np.asarray(metrics.loss)  # serial chain forces all calls
    dt = time.perf_counter() - t0
    assert np.all(np.isfinite(final_loss)), "non-finite loss in bench"

    rate = calls * K / dt
    return {
        "learner_steps_per_sec": round(rate, 1),
        "us_per_step": round(dt / (calls * K) * 1e6, 1),
        "samples_per_sec": round(rate * B),
        "config": {
            "batch_size": B,
            "steps_per_call": K,
            "capacity": C,
            "sampler": "two_level",
            "sample_ahead": not args.strict_per,
            "second_moment_dtype": "bfloat16",
            "target_dtype": "bfloat16",
            "param_dtype": args.param_dtype,
            "chip": jax.devices()[0].device_kind,
        },
        "note": "forced by a host read of the final loss",
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps-per-call", type=int, default=2048)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--capacity", type=int, default=100_000)
    parser.add_argument("--timed-calls", type=int, default=8)
    parser.add_argument(
        "--strict-per", action="store_true",
        help="sequential PER (sample/restamp every step in-scan) instead of "
        "the batched sample-ahead mode (device_replay_sample_many)",
    )
    parser.add_argument(
        "--param-dtype", default="float32", choices=("bfloat16", "float32"),
        help="network param storage dtype (bfloat16 pairs with a float32 "
        "master copy in the optimizer — train_step.with_float32_master)",
    )
    parser.add_argument(
        "--skip-sampler-validation", action="store_true",
        help="skip the 2M-slot sampler parity check (saves ~30s)",
    )
    parser.add_argument(
        "--skip-pipeline", action="store_true",
        help="skip the end-to-end async-pipeline run (actors + infeed + "
        "fused learner contending on the chip; ~90s)",
    )
    parser.add_argument("--pipeline-steps", type=int, default=16_384)
    parser.add_argument(
        "--pipeline-trials", type=int, default=3,
        help="trials per pipeline mode; the report carries the median run "
        "+ per-trial numbers + spread",
    )
    parser.add_argument(
        "--skip-host-dedup", action="store_true",
        help="skip the 2M native dedup host-replay bench (~17.6 GB RAM)",
    )
    parser.add_argument(
        "--host-replay-capacity", type=int, default=2_000_000,
        help="slots for the host sum-tree replay bench; NB the raw frame "
        "stores preallocate ~14 MB per 1000 slots (28 GB at the 2M "
        "default) — shrink on small-RAM machines",
    )
    parser.add_argument("--skip-serving", action="store_true",
                        help="skip the serving_qps loadgen section")
    parser.add_argument("--serving-clients", type=int, default=32)
    parser.add_argument("--serving-duration", type=float, default=6.0)
    parser.add_argument("--serving-network", default="conv",
                        choices=("conv", "nature", "mlp"))
    parser.add_argument("--serving-max-batch", type=int, default=32)
    parser.add_argument("--skip-serving-net", action="store_true",
                        help="skip the socket serving-tier scale-out "
                        "section (1-vs-2 replica subprocess fleets)")
    parser.add_argument("--serving-net-clients", type=int, default=4,
                        help="closed-loop clients PER replica for "
                        "serving_net")
    parser.add_argument("--serving-net-duration", type=float, default=6.0)
    parser.add_argument("--serving-net-network", default="mlp",
                        choices=("conv", "nature", "mlp"))
    parser.add_argument("--serving-net-env", default="random:84x84x1")
    parser.add_argument("--skip-ckpt-stall", action="store_true",
                        help="skip the checkpoint_stall section (2M-slot "
                        "native dedup ring: ~17.6 GB RAM + a one-off "
                        "multi-GB full-snapshot disk write)")
    parser.add_argument("--ckpt-capacity", type=int, default=2_000_000,
                        help="slots for the checkpoint_stall dedup layout")
    parser.add_argument("--ckpt-interval-rows", type=int, default=65_536,
                        help="transitions ingested between incremental "
                        "saves (the checkpoint interval the delta covers)")
    parser.add_argument(
        "--ckpt-stall-only", action="store_true",
        help="run ONLY the checkpoint_stall section and print its JSON "
        "(artifact generation: demos/ckpt_stall.json)",
    )
    parser.add_argument("--skip-pipeline-overlap", action="store_true",
                        help="skip the overlapped-dispatch pipeline sweep "
                        "(CPU-pinned subprocess; depth 1/2/4)")
    parser.add_argument("--pipeline-overlap-steps", type=int, default=6400)
    parser.add_argument("--pipeline-overlap-sync-every", type=int,
                        default=1024)
    parser.add_argument("--skip-xp-transport", action="store_true",
                        help="skip the shm-ring vs mp.Queue transport bench")
    parser.add_argument("--skip-xp-net", action="store_true",
                        help="skip the shm-ring vs TCP-loopback transport "
                        "bench (xp_net)")
    parser.add_argument("--xp-workers", default="4,16,64",
                        help="comma-separated producer counts for "
                        "xp_transport")
    parser.add_argument("--xp-seconds", type=float, default=3.0)
    parser.add_argument("--skip-replay-svc", action="store_true",
                        help="skip the replay-as-a-service RPC vs "
                        "in-process section")
    parser.add_argument("--replay-svc-iters", type=int, default=300)
    parser.add_argument("--replay-svc-capacity", type=int, default=16_384)
    parser.add_argument("--replay-svc-rows", type=int, default=8_192)
    parser.add_argument("--skip-central-inference", action="store_true",
                        help="skip the central_inference section "
                        "(paramless vs param-holding workers at "
                        "4/16/64 — the longest host-only section: the "
                        "64-wide legs ramp a real process fleet)")
    parser.add_argument("--central-widths", default="4,16,64")
    parser.add_argument("--central-measure-s", type=float, default=20.0)
    parser.add_argument("--central-skip-kill", action="store_true",
                        help="skip the central_inference replica-kill "
                        "leg (the subprocess smoke; CI-tiny bench runs "
                        "keep the width points only)")
    parser.add_argument("--skip-replay-tiered", action="store_true",
                        help="skip the replay_tiered section (disk-spill "
                        "cold frame store vs in-core)")
    parser.add_argument("--replay-tiered-capacity", type=int,
                        default=200_000)
    parser.add_argument("--replay-tiered-iters", type=int, default=1000)
    parser.add_argument(
        "--replay-tiered-only", action="store_true",
        help="run ONLY the replay_tiered section and print its JSON "
        "(the demos/replay_tiered.json artifact)",
    )
    parser.add_argument(
        "--xp-transport-smoke", action="store_true",
        help="CI gate: run ONLY a tiny xp_transport point + barrage "
        "(host-only, no jax, seconds not minutes) and exit — "
        "tools/verify_t1.sh uses this so an import-time regression in the "
        "transport can't reach the driver unseen",
    )
    args = parser.parse_args()

    if args.replay_tiered_only:
        print(json.dumps({"replay_tiered": _replay_tiered_bench(
            capacity=args.replay_tiered_capacity,
            iters=args.replay_tiered_iters,
        )}))
        return

    if args.ckpt_stall_only:
        print(json.dumps({"checkpoint_stall": _checkpoint_stall_bench(
            capacity=args.ckpt_capacity,
            interval_rows=args.ckpt_interval_rows,
        )}))
        return

    if args.xp_transport_smoke:
        out = _xp_transport_bench(workers=(2,), seconds=0.5, rows=16,
                                  obs_shape=(16, 16, 1), barrage_rounds=1)
        bar = out["sigkill_barrage"]
        assert bar["lost_committed_chunks"] == 0, bar
        assert bar["seq_errors"] == 0, bar
        print(json.dumps({"xp_transport_smoke": out}))
        return

    extra: dict = {}

    def section(key, fn, *a, **kw):
        """Fault isolation for the HOST-ONLY sections: a failing/slow one
        records its error instead of losing the whole (single-line) bench
        output.  The device sections never run through here."""
        try:
            extra[key] = fn(*a, **kw)
        except Exception as e:  # noqa: BLE001 — recorded, not fatal
            extra[key] = {"error": f"{type(e).__name__}: {e}"}

    # The device sections are what this benchmark is for.  They run
    # directly: no TPU, or an exception in any of them, ends the run
    # non-zero with no result line — a CPU must never supply a line that
    # reads like a chip's.  This process initialises jax here, once, and
    # owns the chip from now on; every child below is CPU-pinned.
    import jax
    import jax.numpy as jnp

    from ape_x_dqn_tpu.parallel.mesh import device_info
    from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = device_info()
    if device["platform"] != "tpu":
        raise SystemExit(
            "bench.py: the device sections need a TPU, jax found "
            f"{device} — nothing measured, no result line"
        )
    extra["fused"] = _fused_headline_bench(args)
    # Dedup twin of the headline: same workload over the frame-dedup
    # HBM ring (each frame once) — config3-scale layout per-step cost.
    extra["dedup_fused"] = _dedup_fused_bench(args, jnp, jax)
    if not args.skip_sampler_validation:
        extra["samplers_2m"] = _validate_samplers(np.random.default_rng(12))
    if not args.skip_sampler_validation:
        section("host_replay_2m", _host_replay_bench,
                capacity=args.host_replay_capacity)
    if not args.skip_host_dedup:
        # Paper-scale host path on the native C++ dedup core: n_stripes=1
        # beside the striped-4 sampling law.
        section("host_dedup_2m", _host_dedup_bench,
                capacity=args.host_replay_capacity)
        section("host_dedup_2m_striped4", _host_dedup_bench,
                capacity=args.host_replay_capacity, n_stripes=4, iters=1000)
    if not args.skip_serving:
        # Host-only like host_replay/host_dedup: the loadgen child is
        # pinned to the CPU.
        section("serving_qps", _serving_bench,
                clients=args.serving_clients,
                duration=args.serving_duration,
                network=args.serving_network,
                max_batch=args.serving_max_batch)
    if not args.skip_serving_net:
        # Host-only like serving_qps: the SOCKET serving tier — 1 vs 2
        # routed replica subprocesses at matched per-replica load, delta
        # param fan-out cost per push (ISSUE 9; demos/serving_net.json is
        # the committed artifact with fault injection on top).
        section("serving_net", _serving_net_bench,
                clients_per_replica=args.serving_net_clients,
                duration=args.serving_net_duration,
                network=args.serving_net_network,
                env=args.serving_net_env)
    if not args.skip_pipeline_overlap:
        # Host-only (CPU-pinned subprocess): the overlapped dispatch
        # pipeline's sync-count / overlap accounting at depth 1/2/4.
        section("pipeline_overlap", _pipeline_overlap_bench,
                steps=args.pipeline_overlap_steps,
                sync_every=args.pipeline_overlap_sync_every)
    if not args.skip_xp_transport:
        # Host-only (no jax in any producer/consumer): the actor→learner
        # transport in isolation, shm ring vs mp.Queue, + SIGKILL barrage.
        section("xp_transport", _xp_transport_bench,
                workers=tuple(int(w) for w in args.xp_workers.split(",")),
                seconds=args.xp_seconds)
    if not args.skip_xp_net:
        # Host-only (no jax in any producer/consumer): shm ring vs the
        # TCP backend over loopback — the cost of leaving /dev/shm
        # (ISSUE 8; demos/xp_net.json is the committed point set).
        section("xp_net", _xp_net_bench,
                workers=tuple(int(w) for w in args.xp_workers.split(",")),
                seconds=args.xp_seconds)
    if not args.skip_replay_tiered:
        # Host-only (no jax): the disk-spill cold frame store vs in-core —
        # sample/update with hot capped at 25% of frames (ROADMAP item 6;
        # demos/replay_tiered.json is the committed paper-scale point).
        section("replay_tiered", _replay_tiered_bench,
                capacity=args.replay_tiered_capacity,
                iters=args.replay_tiered_iters)
    if not args.skip_replay_svc:
        # Host-only (CPU-pinned subprocess; no jax anywhere in it): the
        # replay-as-a-service RPC plane vs in-process sampling — what
        # moving the replay out of the learner's address space costs per
        # batch (ROADMAP item 1; demos/replay_svc.json is the committed
        # point set).
        section("replay_svc", _replay_svc_bench,
                iters=args.replay_svc_iters,
                capacity=args.replay_svc_capacity,
                rows=args.replay_svc_rows)
    if not args.skip_central_inference:
        # Host-only (CPU-pinned subprocess): SEED-style paramless
        # workers vs param-holding ones at fleet width — env-steps/s
        # through the serving tier's micro-batcher, rtt percentiles,
        # and the replica-kill leg (ROADMAP item 2;
        # demos/central_inference.json is the committed point set).
        section("central_inference", _central_inference_bench,
                widths=args.central_widths,
                measure_s=args.central_measure_s,
                skip_kill_leg=args.central_skip_kill)
    if not args.skip_ckpt_stall:
        # Host-only: learner-visible checkpoint stall, full-sync vs the
        # incremental async subsystem, at the 2M-slot dedup layout.
        section("checkpoint_stall", _checkpoint_stall_bench,
                capacity=args.ckpt_capacity,
                interval_rows=args.ckpt_interval_rows)
    if not args.skip_pipeline:
        # Device sections again (the pipelines put the learner, and in
        # thread mode the actors, on the chip): direct calls, a failure
        # fails the bench.
        extra["actor_solo"] = _actor_solo_bench()
        extra["pipeline"] = _median_pipeline(
            args.pipeline_trials, learner_steps=args.pipeline_steps
        )
        # Second north-star metric: actor FPS.  The solo number is the
        # capability ceiling; the contended pipeline numbers show what one
        # chip sustains with the learner sharing the device queue.
        extra["actor_fps"] = extra["actor_solo"]["actor_fps"]
        # The designed mitigation: CPU-only worker-process actors leave the
        # device to the learner alone.  Learner steps/s should recover
        # toward the solo fused figure; actor FPS is host-core-bound.  Two
        # load points: under full worker load the learner's host dispatch
        # thread competes with worker inference for cores; with a light
        # fleet it should recover most of the solo rate — the device is the
        # learner's alone in both.
        extra["pipeline_process"] = _median_pipeline(
            args.pipeline_trials,
            learner_steps=32_768,
            steps_per_call=2048,
            actor_mode="process",
            num_workers=4,
            num_actors=256,
            min_replay=10_000,
        )
        extra["pipeline_process_light"] = _pipeline_bench(
            63_488,
            steps_per_call=2048,
            publish_every=16_384,
            actor_mode="process",
            num_workers=1,
            num_actors=8,
            min_replay=2_000,
            worker_nice=19,
        )
        # End-to-end DEDUP pipeline (thread mode, dedup HBM ring fed by
        # dedup-emitting actors) — the config3 storage layout live on the
        # chip; one trial (time-bounded), compare `pipeline`'s median.
        extra["pipeline_dedup"] = _pipeline_bench(
            args.pipeline_steps, dedup=True
        )
        # process_vs_thread: a MATCHED pair — same 256 actors, same 32768
        # learner steps, same steps_per_call, median of the same number of
        # trials.  Thread-mode actors run jitted policy forwards on the
        # learner's device; process-mode workers are CPU-only (pinned and
        # checked in the child before any backend init; chunks ride the
        # shm-ring transport).
        extra["pipeline_thread_matched"] = _median_pipeline(
            args.pipeline_trials,
            learner_steps=32_768,
            steps_per_call=2048,
            num_actors=256,
            min_replay=10_000,
        )
        p_thread = extra["pipeline_thread_matched"][
            "median_window_steps_per_sec"]
        p_proc = extra["pipeline_process"]["median_window_steps_per_sec"]
        extra["process_vs_thread"] = {
            "thread_median": p_thread,
            "process_median": p_proc,
            "winner": "process" if p_proc > p_thread else "thread",
            "process_beats_thread": bool(p_proc > p_thread),
            "matched_config": {
                "num_actors": 256, "learner_steps": 32_768,
                "steps_per_call": 2048, "min_replay": 10_000,
                "trials": args.pipeline_trials,
            },
            "note": (
                "medians of the steady-state window rate over "
                f"{args.pipeline_trials} matched trials per mode "
                "(pipeline_thread_matched vs pipeline_process); workers "
                "are truly CPU-only in process mode"
            ),
        }
        extra["pipeline_process"]["note"] = (
            "4 CPU-inference workers × 64 actors each: the learner's host "
            "thread shares the host's cores with worker inference (the "
            "device itself is uncontended — that is what process mode "
            "fixes); see pipeline_process_light for the same runtime under "
            "light worker load"
        )

    rate = extra["fused"]["learner_steps_per_sec"]
    print(
        json.dumps(
            {
                "metric": "learner_steps_per_sec",
                "value": rate,
                "unit": "steps/s",
                "vs_baseline": round(rate / NORTH_STAR_PER_CHIP, 3),
                "device": device,
                **extra,
            }
        )
    )


if __name__ == "__main__":
    main()
