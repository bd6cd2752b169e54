"""Price central (SEED-style) vs local action selection at fleet width —
the number ROADMAP item 2 asked for: what do paramless actors cost (or
buy) in env-steps/s when action selection moves into the serving tier's
micro-batcher?

For each width W (default 4/16/64 worker processes, 1 actor each, the
84x84x1 random env + mlp Q-net): two matched runs driven WITHOUT a
learner so the number isolates the actor plane —

  * ``local`` — every worker holds a param snapshot (shm seqlock
    buffer) and runs its own jitted policy_step; the driver republishes
    params every ``--publish-s`` seconds (the fan-out tax at width);
  * ``central`` — workers hold NOTHING; each env step ships the obs
    batch as pipelined F_IREQ requests into a PolicyServer micro-batcher
    hosted by the DRIVER process (the trainer's auto mode), replies
    carry greedy actions + q + param_version; the same publish cadence
    feeds the server's hot reload.

Aggregate env-steps/s is measured over a fixed window after a ramp gate
(all workers flowing, or the bounded ramp timeout — 64 jax imports on a
1-core host take minutes; the gate keeps the window honest and MATCHED
between modes).  On a 1-core host both modes share one CPU: the central
legs price the inversion's batching economy against its socket round
trips, not network latency — the xp_net caveat, on the inference plane.

The ``replica_kill`` leg embeds tools/central_inference_smoke.py's
verdict (run as a subprocess): a 2-replica routed fleet takes a mid-run
SIGKILL under live paramless training — zero torn frames, zero drops,
training continues.  Output: one JSON line (committed as
demos/central_inference.json).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_cfg(width: int, inference: str):
    from ape_x_dqn_tpu.config import ApexConfig

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "random:84x84x1"
    cfg.actor.mode = "process"
    cfg.actor.num_workers = width
    cfg.actor.num_actors = 2 * width      # 2 actors/worker: the inflight
    #                                       split has rows to pipeline
    cfg.actor.T = 100_000_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 16
    cfg.actor.spawn_stagger_s = 0.05 if width >= 16 else 0.0
    cfg.actor.xp_ring_bytes = 4 << 20
    cfg.actor.inference = inference
    cfg.actor.inference_inflight = 2
    cfg.actor.inference_codec = "zlib"
    cfg.serving.max_batch = 16
    cfg.serving.max_wait_ms = 3.0
    cfg.serving.queue_capacity = 256
    return cfg.validate()


def _run_leg(width: int, inference: str, measure_s: float,
             ramp_timeout_s: float, publish_s: float) -> dict:
    """One width x mode point: pool + (central: in-process serving tier),
    no learner — poll/drain on the driver thread, publish on cadence."""
    import secrets

    import jax

    from ape_x_dqn_tpu.runtime.param_store import ParamStore
    from ape_x_dqn_tpu.runtime.process_actors import (
        ProcessActorPool,
        network_and_template,
    )

    cfg = _make_cfg(width, inference)
    _, network, template = network_and_template(cfg)
    host_params = jax.device_get(template)
    pool = ProcessActorPool(cfg, num_workers=width)
    server = net = None
    store = None
    try:
        if inference == "central":
            from ape_x_dqn_tpu.serving.net_server import ServingNetServer
            from ape_x_dqn_tpu.serving.server import PolicyServer

            token = secrets.randbits(63) or 1
            store = ParamStore(host_params)
            server = PolicyServer(
                network, params=host_params, param_source=store,
                max_batch=cfg.serving.max_batch,
                max_wait_ms=cfg.serving.max_wait_ms,
                queue_capacity=cfg.serving.queue_capacity,
            )
            server.warmup((84, 84, 1))
            server.start()
            net = ServingNetServer(server, run_token=token).start()
            pool.set_inference_endpoint("127.0.0.1", net.port, token)
        else:
            pool.publish(host_params)
        t_spawn = time.monotonic()
        pool.start()

        def flowing() -> int:
            ws = pool.worker_stats(max_age_s=0.0)
            return sum(1 for w in ws.values() if w.get("env_steps", 0) > 0)

        # Ramp gate: all workers flowing, or the bounded timeout.
        deadline = time.monotonic() + ramp_timeout_s
        while time.monotonic() < deadline:
            pool.poll(max_items=256)
            pool.supervise()
            if flowing() >= width:
                break
            time.sleep(0.1)
        ramp_s = time.monotonic() - t_spawn
        flowing_at_gate = flowing()

        def steps_now() -> int:
            ws = pool.worker_stats(max_age_s=0.0)
            return sum(int(w.get("env_steps", 0)) for w in ws.values())

        next_publish = time.monotonic() + publish_s
        s0, t0 = steps_now(), time.monotonic()
        while time.monotonic() - t0 < measure_s:
            pool.poll(max_items=256)
            pool.supervise()
            if time.monotonic() >= next_publish:
                # The param path under test: local = fan-out to every
                # worker; central = one store publish the server reloads.
                if inference == "central":
                    store.publish(host_params)
                else:
                    pool.publish(host_params)
                next_publish += publish_s
            time.sleep(0.005)
        s1, t1 = steps_now(), time.monotonic()
        leg = {
            "workers": width,
            "inference": inference,
            "env_steps_per_s": round((s1 - s0) / (t1 - t0), 1),
            "measure_s": round(t1 - t0, 1),
            "ramp_s": round(ramp_s, 1),
            "flowing_at_gate": flowing_at_gate,
            "worker_restarts": pool.restarts,
        }
        if inference == "central":
            inf = pool.inference_stats()
            leg["rtt_ms"] = inf["rtt"]
            leg["torn_replies"] = inf["torn_replies"]
            leg["retries"] = inf["retries"]
            leg["wire_over_logical"] = inf["wire_over_logical"]
            leg["server"] = {
                k: net.stats()[k]
                for k in ("inference_requests", "inference_rows",
                          "torn_frames", "shed")
            }
            hist = server.batcher.batch_hist
            total = sum(hist.values())
            leg["batch_occupancy_mean"] = (
                round(sum(k * c for k, c in hist.items()) / total, 2)
                if total else None
            )
        else:
            tr = pool.transport_stats()
            leg["torn_replies"] = 0
            leg["param_buffer_bytes"] = (
                pool.buffer.capacity if pool.buffer is not None else 0
            )
            leg["transitions_s"] = tr.get("transitions_s")
        return leg
    finally:
        pool.stop()
        if net is not None:
            net.close()
        if server is not None:
            server.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="central_inference_bench")
    ap.add_argument("--widths", default="4,16,64")
    ap.add_argument("--measure-s", type=float, default=20.0)
    ap.add_argument("--ramp-timeout-s", type=float, default=300.0)
    ap.add_argument("--publish-s", type=float, default=2.0)
    ap.add_argument("--skip-kill-leg", action="store_true")
    ap.add_argument("--out", default="-")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")

    widths = [int(w) for w in args.widths.split(",") if w]
    report = {
        "config": {
            "widths": widths, "measure_s": args.measure_s,
            "env": "random:84x84x1", "network": "mlp",
            "actors_per_worker": 2, "inflight": 2,
            "inference_codec": "zlib", "publish_s": args.publish_s,
        },
        "points": [],
    }
    for w in widths:
        for mode in ("local", "central"):
            leg = _run_leg(w, mode, args.measure_s, args.ramp_timeout_s,
                           args.publish_s)
            report["points"].append(leg)
            print(f"# {json.dumps(leg)}", file=sys.stderr)
    by = {(p["workers"], p["inference"]): p for p in report["points"]}
    for w in widths:
        loc = by.get((w, "local"))
        cen = by.get((w, "central"))
        if loc and cen and loc["env_steps_per_s"]:
            cen["vs_local"] = round(
                cen["env_steps_per_s"] / loc["env_steps_per_s"], 3
            )

    if not args.skip_kill_leg:
        # The fault-tolerance leg: the verify-gate smoke as a subprocess
        # (2 serve.py replicas behind the router, paramless training
        # through a mid-run SIGKILL).
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable,
             os.path.join(repo, "tools", "central_inference_smoke.py")],
            capture_output=True, text=True, timeout=460.0, env=env,
            cwd=repo,
        )
        try:
            report["replica_kill"] = json.loads(
                proc.stdout.strip().splitlines()[-1]
            )
        except (ValueError, IndexError):
            report["replica_kill"] = {
                "ok": False, "rc": proc.returncode,
                "stderr_tail": (proc.stderr or "")[-300:],
            }

    report["note"] = (
        "1-core host: both modes share one CPU, so the central legs "
        "price the batching inversion against socket round trips, not "
        "network latency; ramp gate bounds the 64-wide jax import storm "
        "out of the measure window"
    )
    line = json.dumps(report)
    if args.out == "-":
        print(line)
    else:
        with open(args.out, "w") as f:
            f.write(line + "\n")
        print(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
