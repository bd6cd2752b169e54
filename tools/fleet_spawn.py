"""Throttled process-actor fleet spawn on the experience transport —
config3's fleet shape (256-wide, 16x16), scaled to whatever VM runs this.

The ROADMAP open item "spawn config3's fleet shape for real" needs three
things proven at fleet width: (1) the fd/shm/socket budget holds, (2) a
throttled spawn brings the whole fleet up without piling every child's
jax import onto the host at once, and (3) a SIGKILL of a worker subset
recovers fully — salvage of every committed chunk, fresh channels for
the respawned incarnations, experience flowing again from every killed
worker id.  This tool runs exactly that and prints one JSON line.

``--transport tcp`` runs the whole fleet over the TCP backend
(runtime/net.py) on loopback — every worker is a NON-shm worker feeding
the same framed record stream a remote host would — and republishes
(slightly perturbed) params on a cadence so the per-version fan-out cost
lands in the report's ``net`` section.

Usage (the committed demo artifacts' producers):

    python tools/fleet_spawn.py --workers 64 --kill 8 --stagger 0.1 \
        --out demos/fleet_spawn.json
    python tools/fleet_spawn.py --transport tcp --workers 16 --actors 256 \
        --kill 4 --stagger 0.25 --out demos/fleet_net.json
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workers", type=int, default=64)
    ap.add_argument("--actors", type=int, default=0,
                    help="global actor count (default: one per worker)")
    ap.add_argument("--kill", type=int, default=8,
                    help="workers to SIGKILL once the fleet is flowing")
    ap.add_argument("--stagger", type=float, default=0.1,
                    help="seconds between worker spawns (throttle)")
    ap.add_argument("--ring-mb", type=float, default=1.0,
                    help="per-worker experience ring size (MB)")
    ap.add_argument("--transport", choices=("shm", "tcp"), default="shm",
                    help="experience transport backend")
    ap.add_argument("--publish-every", type=float, default=2.0,
                    help="seconds between param republishes while flowing "
                    "(tcp: measures per-version fan-out cost)")
    ap.add_argument("--env", default="chain:6")
    ap.add_argument("--network", default="mlp")
    ap.add_argument("--flow-timeout", type=float, default=1800.0,
                    help="deadline for every worker's first chunk")
    ap.add_argument("--out", default="-")
    args = ap.parse_args()

    # CPU-only end to end: the fleet tool measures the host transport and
    # must not take a chip a trainer may own.  Workers inherit the variable.
    os.environ["JAX_PLATFORMS"] = "cpu"  # before the first jax import

    from ape_x_dqn_tpu.config import ApexConfig, transport_budget
    from ape_x_dqn_tpu.runtime.process_actors import (
        ProcessActorPool,
        network_and_template,
    )

    cfg = ApexConfig()
    cfg.network = args.network
    cfg.env.name = args.env
    cfg.actor.mode = "process"
    cfg.actor.num_workers = args.workers
    cfg.actor.num_actors = args.actors or args.workers
    cfg.actor.T = 1_000_000_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 64
    cfg.actor.worker_nice = 10
    cfg.actor.xp_ring_bytes = int(args.ring_mb * (1 << 20))
    cfg.actor.spawn_stagger_s = args.stagger
    cfg.actor.transport = args.transport
    cfg.validate()

    report: dict = {
        "workers": args.workers,
        "actors": cfg.actor.num_actors,
        "width": f"{args.workers}x{cfg.actor.num_actors // args.workers}",
        "transport": args.transport,
        "stagger_s": args.stagger,
        "planned_budget": transport_budget(cfg),
    }
    pool = ProcessActorPool(cfg, num_workers=args.workers,
                            max_restarts=args.kill + 2)
    try:
        import jax.tree_util as jtu

        _, _, template = network_and_template(cfg)
        pool.publish(template)
        t0 = time.monotonic()
        pool.start()
        report["spawn_s"] = round(time.monotonic() - t0, 2)
        report["accounting_after_spawn"] = pool.shm_accounting()
        next_pub = [time.monotonic() + args.publish_every]
        pub_n = [0]

        def maybe_republish():
            # Perturbed republish at the cadence: each push is a fresh
            # version the transport must fan out (tcp: delta-or-full
            # framed messages, cost recorded per push).
            if not args.publish_every \
                    or time.monotonic() < next_pub[0]:
                return
            next_pub[0] = time.monotonic() + args.publish_every
            pub_n[0] += 1
            eps = 1e-6 * pub_n[0]
            pool.publish(jtu.tree_map(lambda x: x + eps, template))

        def drain_until(cond, timeout_s, label):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                pool.supervise()
                pool.poll(max_items=512, timeout=0.05)
                maybe_republish()
                if cond():
                    return
                if pool.worker_errors:
                    raise RuntimeError(
                        f"fatal worker errors during {label}: "
                        f"{pool.worker_errors}"
                    )
            raise TimeoutError(f"{label} did not complete in {timeout_s}s")

        all_wids = set(range(args.workers))
        drain_until(lambda: set(pool.last_versions) == all_wids,
                    args.flow_timeout, "first-chunk-from-every-worker")
        report["all_flowing_s"] = round(time.monotonic() - t0, 2)

        victims = sorted(all_wids)[:args.kill]
        steps_before = {w: pool._steps_by_worker.get(w, 0) for w in victims}
        for w in victims:
            os.kill(pool._procs[w].pid, signal.SIGKILL)
        for w in victims:
            pool._procs[w].join(15.0)
        t_kill = time.monotonic()
        drain_until(
            lambda: all(pool._steps_by_worker.get(w, 0) > steps_before[w]
                        for w in victims),
            args.flow_timeout, "recovery-after-kill",
        )
        report["killed"] = len(victims)
        report["recovery_s"] = round(time.monotonic() - t_kill, 2)
        report["restarts"] = pool.restarts
        report["recovered"] = True
        report["accounting_after_recovery"] = pool.shm_accounting()
        report["transport_stats"] = pool.transport_stats()
        net = pool.net_stats()
        if net:
            report["net"] = net
        report["param_publishes"] = pub_n[0] + 1
    finally:
        pool.stop(join_timeout=60.0)
    report["accounting_after_stop"] = pool.shm_accounting()
    report["total_actor_steps"] = pool.actor_steps
    line = json.dumps(report)
    if args.out == "-":
        print(line)
    else:
        with open(args.out, "w") as f:
            f.write(line + "\n")
        print(line)


if __name__ == "__main__":
    main()
