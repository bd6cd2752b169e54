#!/usr/bin/env python
"""Central-inference smoke gate (tools/verify_t1.sh gate 10).

The SEED-style production story, CI-sized, end to end on REAL processes
and real sockets: a training run whose actors hold NO params and select
every action through the serving tier — with the serving tier being a
routed replica fleet that takes a mid-run SIGKILL.

  1. a 2-replica ServingFleet comes up on ephemeral ports (router +
     delta param hub), each replica a full ``-m ape_x_dqn_tpu.serve``
     child started with the trainer's ``--run-token``;
  2. the trainer (AsyncPipeline, actor.mode=process) spawns a small
     fleet of PARAMLESS workers (actor.inference=central) that dial the
     ROUTER: every env step's observation batch rides CRC-framed
     pipelined F_IREQ requests into a replica's micro-batcher, the
     reply carries greedy actions + q rows + param_version, ε stays
     worker-side on the global ladder slice;
  3. the trainer's publishes are fanned to the fleet as page-deltas
     (the hub), so replies carry ADVANCING param versions — the hot
     reload observable, asserted per-reply from the worker side;
  4. one replica is SIGKILLed MID-RUN: the router drains it, the
     workers' clients reconnect through the router to the survivor and
     retry whole — TRAINING CONTINUES (that is the check: the learner
     reaches its step target, no worker dies, nothing wedges);
  5. the fleet supervisor respawns the dead replica, it re-enters
     rotation and full-syncs from the hub;
  6. verdict: target steps reached, zero torn frames on EITHER side
     (client reply streams AND replica request planes), zero worker
     deaths, replies fresh (version floor advanced past several
     reloads), respawn observed.

    python tools/central_inference_smoke.py
"""

from __future__ import annotations

import argparse
import json
import os
import secrets
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="central_inference_smoke")
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--kill-at-step", type=int, default=100)
    ap.add_argument("--deadline", type=float, default=420.0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from ape_x_dqn_tpu.config import ApexConfig, apply_overrides
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.runtime.process_actors import network_and_template
    from ape_x_dqn_tpu.serving import ServingFleet
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    overrides = [
        "network=mlp", "env.name=chain:6",
        "serving.max_batch=8", "serving.max_wait_ms=3.0",
    ]
    cfg = ApexConfig()
    apply_overrides(cfg, overrides)
    cfg.actor.mode = "process"
    cfg.actor.num_workers = args.workers
    cfg.actor.num_actors = 2 * args.workers
    cfg.actor.T = 1_000_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 16
    cfg.actor.inference = "central"
    cfg.actor.inference_inflight = 2
    cfg.actor.inference_codec = "zlib"
    cfg.learner.min_replay_mem_size = 256
    cfg.learner.publish_every = 5
    cfg.learner.total_steps = args.steps
    cfg.learner.optimizer = "adam"
    cfg.replay.capacity = 8192
    cfg.validate()

    token = secrets.randbits(63) or 1
    events: list = []
    fleet = ServingFleet(
        replicas=2, probe_interval_s=0.25,
        replica_args=[
            *(a for ov in overrides for a in ("--set", ov)),
            "--run-token", str(token),
        ],
        on_event=lambda kind, **f: events.append({"event": kind, **f}),
    )
    # Replicas need a first publish to serve from; same config + seed =
    # the same init params the trainer starts with.
    _, _, template = network_and_template(cfg)
    params0 = jax.tree_util.tree_map(np.array, jax.device_get(template))
    fleet.publish(params0)

    verdict = {"ok": False}
    t_start = time.monotonic()

    def remaining() -> float:
        return args.deadline - (time.monotonic() - t_start)

    pipe = None
    try:
        fleet.start(timeout=min(240.0, remaining()))
        # Paramless workers dial the ROUTER (the fleet front door).
        cfg.actor.inference_host = "127.0.0.1"
        cfg.actor.inference_port = fleet.port
        cfg.actor.inference_token = token

        pipe = AsyncPipeline(
            cfg, logger=MetricLogger(stream=open(os.devnull, "w")),
            log_every=100,
        )
        result: dict = {}
        error: list = []

        def trainer():
            try:
                result["final"] = pipe.run(
                    learner_steps=args.steps,
                    warmup_timeout=min(240.0, remaining()),
                )
            except BaseException as e:  # noqa: BLE001 — verdict material
                error.append(f"{type(e).__name__}: {e}")

        t = threading.Thread(target=trainer, name="trainer", daemon=True)
        t.start()

        # Param relay: trainer publishes -> hub fans page-deltas to the
        # replica fleet (the hot-reload path the workers observe
        # per-reply).  Plus the seeded mid-run replica SIGKILL.
        have = 0
        pushes = 0
        killed_pid = None
        torn_live = None   # replica torn counts scraped MID-RUN, well
        #                    after the kill: the wire-integrity check
        #                    (a straggler worker terminated by teardown
        #                    can die mid-frame afterwards — that is torn
        #                    DETECTION working, not a training-time tear)
        scrape_at = args.kill_at_step + (args.steps - args.kill_at_step) // 2
        while t.is_alive() and remaining() > 0:
            got = pipe.store.get(have)
            if got is not None:
                params, have = got
                fleet.publish(params)
                pushes += 1
            if killed_pid is None and pipe.learner_step >= args.kill_at_step:
                killed_pid = fleet.replicas[0].pid
                fleet.replicas[0].kill()
            if torn_live is None and killed_pid is not None \
                    and pipe.learner_step >= scrape_at:
                torn_live = {
                    str(rid): (((v or {}).get("serving") or {})
                               .get("net") or {}).get("torn_frames")
                    for rid, v in fleet.replica_varz().items()
                }
            time.sleep(0.2)
        t.join(timeout=max(5.0, remaining()))

        # Respawned replica back with fresh ports?
        respawned = False
        while remaining() > 0:
            rep = fleet.replicas[0]
            if rep.alive() and rep.port is not None \
                    and rep.obs_port is not None:
                respawned = True
                break
            time.sleep(0.25)

        final = result.get("final") or {}
        inf = final.get("inference") or {}
        pool = pipe.worker.pool
        # Replica-side torn counts ride /varz serving.net.
        torn = {
            str(rid): (((v or {}).get("serving") or {}).get("net") or {})
            .get("torn_frames")
            for rid, v in fleet.replica_varz().items()
        }
        sources = {
            str(rid): (((v or {}).get("serving") or {}).get("net") or {})
            .get("sources")
            for rid, v in fleet.replica_varz().items()
        }
        st = fleet.stats()
        checks = {
            "trainer_finished": not error and bool(final),
            "target_steps_reached": final.get("step", 0) >= args.steps,
            "workers_all_reported": (
                inf.get("workers_reporting") == args.workers
            ),
            "actions_flowed_centrally": inf.get("replies", 0) > 100,
            "zero_torn_replies_client": inf.get("torn_replies", 1) == 0,
            "zero_torn_frames_replicas": torn_live is not None and all(
                (v or 0) == 0 for v in torn_live.values()
            ),
            "zero_worker_deaths": pool.restarts == 0
            and not pool.worker_errors,
            "replies_fresh_after_reload": (
                inf.get("param_version", -1) >= 3
            ),
            "replica_killed_and_respawned": (
                killed_pid is not None and respawned
                and st["respawns"] >= 1
            ),
            "paramless_pool": pool.store is None and pool.buffer is None,
        }
        verdict = {
            "ok": all(checks.values()),
            "checks": checks,
            "error": error or None,
            "learner_steps": final.get("step"),
            "inference": {
                k: inf.get(k)
                for k in ("selects", "requests", "replies", "retries",
                          "reconnects", "torn_replies", "outages",
                          "stall_ms", "param_version", "rtt",
                          "wire_over_logical")
            },
            "param_pushes_to_fleet": pushes,
            "killed_pid": killed_pid,
            "respawns": st["respawns"],
            "replica_torn_frames_live": torn_live,
            "replica_torn_frames_final": torn,
            "replica_sources": sources,
            "router": st["router"],
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
    finally:
        if pipe is not None:
            pipe.stop_event.set()
        fleet.stop()

    print(json.dumps(verdict))
    return 0 if verdict.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
