#!/usr/bin/env python
"""Serving-net smoke gate (tools/verify_t1.sh gate 8).

The network serving tier's end-to-end contract, CI-sized, on REAL
subprocess replicas and real sockets:

  1. a 2-replica ServingFleet comes up on ephemeral ports (router +
     delta param hub), each replica a full ``-m ape_x_dqn_tpu.serve``
     child announcing its ports over JSONL;
  2. a closed-loop client burst drives the router while a hot param
     reload is published MID-BURST — the push must reach the fleet as a
     page-delta (bytes ≪ full snapshot) and replies must start carrying
     the new ``param_version`` with ZERO dropped requests;
  3. one replica is SIGKILLed mid-burst: the router drains it (no new
     connections), displaced clients reconnect to the live replica and
     retry in flight — still zero drops;
  4. the supervisor respawns the dead replica; it re-enters rotation
     and full-syncs on connect, after which a further publish reaches
     BOTH replicas (fresh ``param_version`` everywhere);
  5. no replica ever counts a torn request frame (client reconnects are
     clean), and the run shuts down with a one-line JSON verdict.

    python tools/serving_net_smoke.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="serving_net_smoke")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--burst-s", type=float, default=6.0)
    ap.add_argument("--deadline", type=float, default=420.0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from ape_x_dqn_tpu.config import ApexConfig, apply_overrides
    from ape_x_dqn_tpu.runtime.components import build_components
    from ape_x_dqn_tpu.serving import (
        ServerOverloaded,
        ServingClient,
        ServingFleet,
    )

    overrides = ["network=mlp", "env.name=chain:6",
                 "serving.max_wait_ms=3.0"]
    cfg = ApexConfig()
    apply_overrides(cfg, overrides)
    cfg.validate()
    comps = build_components(cfg)
    obs_shape = comps.obs_shape

    events: list = []
    fleet = ServingFleet(
        replicas=2, probe_interval_s=0.25,
        replica_args=[a for ov in overrides for a in ("--set", ov)],
        on_event=lambda kind, **f: events.append({"event": kind, **f}),
    )
    params = jax.tree_util.tree_map(
        np.array, jax.device_get(comps.state.params)
    )
    fleet.publish(params)

    verdict = {"ok": False}
    t_start = time.monotonic()

    def remaining() -> float:
        return args.deadline - (time.monotonic() - t_start)

    try:
        fleet.start(timeout=min(240.0, remaining()))

        # -- burst + mid-burst reload + mid-burst SIGKILL ------------------
        stop = threading.Event()
        counts = [0] * args.clients
        drops = [0] * args.clients
        shed = [0] * args.clients
        fresh_seen = [0] * args.clients   # replies carrying version >= 2

        def client(i: int) -> None:
            crng = np.random.default_rng(100 + i)
            c = ServingClient("127.0.0.1", fleet.port, seed=i)
            while not stop.is_set():
                obs = crng.integers(0, 255, obs_shape, dtype=np.uint8)
                try:
                    r = c.act(obs, timeout=60.0)
                    counts[i] += 1
                    if r.param_version >= 2:
                        fresh_seen[i] += 1
                except ServerOverloaded:
                    shed[i] += 1
                    time.sleep(0.005)
                except Exception:  # noqa: BLE001 — a drop, counted
                    drops[i] += 1
            c.close()

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(args.clients)]
        for t in threads:
            t.start()

        time.sleep(args.burst_s * 0.25)
        # Hot reload mid-burst: perturb one leaf -> real dirty pages.
        leaf = jax.tree_util.tree_leaves(params)[1]
        leaf += np.float32(1e-3)
        push = fleet.publish(params)          # version 2, delta expected
        time.sleep(args.burst_s * 0.15)
        killed_pid = fleet.replicas[0].pid
        fleet.replicas[0].kill()              # SIGKILL mid-burst
        time.sleep(args.burst_s * 0.6)
        stop.set()
        for t in threads:
            t.join(timeout=90.0)

        # -- respawn settles; a further publish reaches BOTH replicas ------
        respawned = False
        while remaining() > 0:
            rep = fleet.replicas[0]
            if rep.alive() and rep.port is not None \
                    and rep.obs_port is not None:
                respawned = True
                break
            time.sleep(0.25)
        leaf += np.float32(1e-3)
        final_push = fleet.publish(params)    # version 3
        fresh_both = False
        replica_pv = {}
        while remaining() > 0:
            replica_pv = {
                str(rid): ((v or {}).get("serving") or {})
                .get("param_version")
                for rid, v in fleet.replica_varz().items()
            }
            if all(pv == fleet.param_version
                   for pv in replica_pv.values()):
                fresh_both = True
                break
            time.sleep(0.25)

        # Replica-side torn counts ride /varz serving.net.
        torn = {
            str(rid): (((v or {}).get("serving") or {}).get("net") or {})
            .get("torn_frames")
            for rid, v in fleet.replica_varz().items()
        }
        st = fleet.stats()
        full_bytes = len(
            __import__(
                "ape_x_dqn_tpu.utils.serialization",
                fromlist=["tree_to_bytes"],
            ).tree_to_bytes(params)
        )
        checks = {
            "requests_served": sum(counts) > 50,
            "zero_drops": sum(drops) == 0,
            "reload_reached_clients": sum(fresh_seen) > 0,
            "reload_was_delta": bool(
                push["delta"] >= 1 and push["bytes"] < full_bytes / 10
            ),
            "replica_respawned": respawned and st["respawns"] >= 1,
            "fresh_param_version_on_both": fresh_both,
            "no_torn_request_frames": all((v or 0) == 0
                                          for v in torn.values()),
            "router_saw_kill": st["router"]["splices_broken"] >= 1
            or st["router"]["probe_failures"] >= 1,
        }
        verdict = {
            "ok": all(checks.values()),
            "checks": checks,
            "requests": sum(counts),
            "drops": sum(drops),
            "shed": sum(shed),
            "fresh_replies": sum(fresh_seen),
            "killed_pid": killed_pid,
            "reload_push": push,
            "final_push": final_push,
            "replica_param_version": replica_pv,
            "torn_frames": torn,
            "respawns": st["respawns"],
            "router": st["router"],
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
    finally:
        fleet.stop()

    print(json.dumps(verdict))
    return 0 if verdict.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
