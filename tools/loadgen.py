#!/usr/bin/env python
"""Closed-loop load generator for the policy-serving subsystem.

N client threads drive an in-process PolicyServer (serving/server.py) in
closed loop — each client submits one observation, waits for its action,
optionally thinks, repeats — the standard shape for measuring a batching
service honestly (open-loop generators overstate a coalescing server's
latency and understate its throughput).

Four phases, one JSON artifact:
  1. **sequential** — batch-1 jitted apply in a plain loop: the throughput
     a client gets WITHOUT the serving tier (the 5x claim's denominator);
  2. **concurrent** — N clients against the server, with ``--reloads`` hot
     param swaps published mid-run (the zero-dropped-on-reload claim);
  3. **low-qps** — a lone client with think time: latency must be bounded
     by the max-wait deadline + one batch-1 apply (the p99 bound claim);
  4. a ``checks`` block asserting all three claims machine-readably.

Usage:
    python tools/loadgen.py --clients 32 --duration 6 \
        --out demos/serving_loadgen.json
The result JSON is always printed as the LAST stdout line.

**Socket mode** (ISSUE 9): the same closed loop over REAL sockets —
``ServingClient`` connections through the replica router
(serving/router.ServingFleet), with reconnect + whole-request retry, so
"zero drops" is measured end to end across hot reloads and replica
SIGKILLs.  Three entry flags:

  * ``--serve-replicas N`` — spawn an N-replica fleet in-process, drive
    it, tear it down; ``--kill-replica-at SEC`` SIGKILLs one replica
    mid-window (the router-recovery measurement);
  * ``--compare-replicas 1,2`` — the scale-out artifact: one fleet per
    width with matched total load (demos/serving_net.json);
  * ``--connect HOST:PORT`` — clients only, against an external fleet.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_obs(spec: str):
    return tuple(int(d) for d in spec.lower().split("x"))


def run_loadgen(
    clients: int = 32,
    duration: float = 6.0,
    think_ms: float = 0.0,
    network: str = "conv",
    obs_shape=(84, 84, 1),
    num_actions: int = 4,
    max_batch: int = 32,
    max_wait_ms: float = 5.0,
    queue_capacity: int = 256,
    seq_seconds: float = 3.0,
    reloads: int = 2,
    low_qps_requests: int = 20,
    seed: int = 0,
) -> dict:
    import jax
    import numpy as np

    from ape_x_dqn_tpu.models.dueling import build_greedy_apply, build_network
    from ape_x_dqn_tpu.runtime.param_store import ParamStore
    from ape_x_dqn_tpu.serving import PolicyServer

    net = build_network(network, num_actions)
    rng = np.random.default_rng(seed)
    dummy = np.zeros((1, *obs_shape), np.uint8)
    params0 = net.init(jax.random.PRNGKey(seed), dummy)
    store = ParamStore(params0)

    server = PolicyServer(
        net,
        param_source=store,
        max_batch=max_batch,
        max_wait_ms=max_wait_ms,
        queue_capacity=queue_capacity,
        reload_poll_s=0.1,
    )
    server.warmup(obs_shape)
    server.start()

    # -- phase 1: sequential batch-1 baseline (no serving tier) -----------
    apply_fn = build_greedy_apply(net)
    params_dev = jax.device_put(jax.device_get(params0))
    obs1 = rng.integers(0, 255, (1, *obs_shape), dtype=np.uint8)
    jax.device_get(apply_fn(params_dev, obs1))  # compile outside the clock
    obs_big = np.broadcast_to(obs1, (max_batch, *obs_shape))
    jax.device_get(apply_fn(params_dev, obs_big))
    t0 = time.perf_counter()
    seq_requests = 0
    while time.perf_counter() - t0 < seq_seconds:
        obs = rng.integers(0, 255, (1, *obs_shape), dtype=np.uint8)
        jax.device_get(apply_fn(params_dev, obs))
        seq_requests += 1
    seq_wall = time.perf_counter() - t0
    seq_qps = seq_requests / seq_wall
    single_apply_ms = seq_wall / max(seq_requests, 1) * 1e3
    # One full-bucket batch's compute (for the p99 bound arithmetic).
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        jax.device_get(apply_fn(params_dev, obs_big))
    batch_apply_ms = (time.perf_counter() - t0) / reps * 1e3

    # -- phase 2: concurrent clients + hot reloads mid-run -----------------
    stop = threading.Event()
    counts = [0] * clients
    shed_errors = [0] * clients
    other_errors = [0] * clients

    def client(i: int) -> None:
        from ape_x_dqn_tpu.serving import ServerOverloaded

        crng = np.random.default_rng(seed + 1000 + i)
        while not stop.is_set():
            obs = crng.integers(0, 255, obs_shape, dtype=np.uint8)
            try:
                server.act(obs, timeout=60.0)
                counts[i] += 1
            except ServerOverloaded:
                shed_errors[i] += 1
            except Exception:  # noqa: BLE001 — counted, loop continues
                other_errors[i] += 1
            if think_ms > 0:
                time.sleep(think_ms / 1e3)

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(clients)
    ]
    served_before = server.stats()["served_total"]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    # Publish `reloads` fresh param sets spread across the run — the
    # training side of hot reload, compressed: each publish is exactly what
    # the learner's capped-rate publish does (runtime/param_store.py).
    for r in range(reloads):
        time.sleep(duration / (reloads + 1))
        fresh = net.init(jax.random.PRNGKey(seed + 7919 * (r + 1)), dummy)
        store.publish(fresh)
    time.sleep(max(0.0, duration - (time.perf_counter() - t0)))
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
    conc_wall = time.perf_counter() - t0
    stats = server.stats()
    conc_requests = sum(counts)
    conc_qps = conc_requests / conc_wall

    # -- phase 3: low-QPS deadline bound -----------------------------------
    low_lat_ms = []
    lrng = np.random.default_rng(seed + 5)
    for _ in range(low_qps_requests):
        obs = lrng.integers(0, 255, obs_shape, dtype=np.uint8)
        res = server.act(obs, timeout=30.0)
        low_lat_ms.append(res.latency_s * 1e3)
        time.sleep(0.02)
    server.close()

    speedup = conc_qps / max(seq_qps, 1e-9)
    p99_ms = stats["latency"].get("p99_ms", float("nan"))
    # Bounds: a lone request may wait the full deadline then one batch-1
    # apply; a loaded request at worst queues behind one in-flight bucket
    # then rides the next (deadline + 2 bucket applies), with scheduler
    # margin on a contended host.
    low_bound_ms = max_wait_ms + 4 * single_apply_ms + 50.0
    p99_bound_ms = max_wait_ms + 4 * batch_apply_ms + 100.0
    result = {
        "config": {
            "clients": clients,
            "duration_s": duration,
            "think_ms": think_ms,
            "network": network,
            "obs_shape": list(obs_shape),
            "num_actions": num_actions,
            "max_batch": max_batch,
            "max_wait_ms": max_wait_ms,
            "queue_capacity": queue_capacity,
            "buckets": server._batcher.buckets,
            "platform": jax.devices()[0].platform,
        },
        "sequential": {
            "qps": round(seq_qps, 1),
            "requests": seq_requests,
            "seconds": round(seq_wall, 2),
            "single_apply_ms": round(single_apply_ms, 3),
            "batch_apply_ms": round(batch_apply_ms, 3),
        },
        "concurrent": {
            "qps": round(conc_qps, 1),
            "requests": conc_requests,
            "served_by_server": stats["served_total"] - served_before,
            "seconds": round(conc_wall, 2),
            "latency": stats["latency"],
            "batch_hist": stats["batch_hist"],
            "shed": sum(shed_errors),
            "errors": sum(other_errors),
        },
        "speedup": round(speedup, 2),
        "reloads": {
            "requested": reloads,
            "observed": server.reload_count,
            "final_version": server.param_version,
        },
        "low_qps": {
            "requests": low_qps_requests,
            "max_ms": round(max(low_lat_ms), 3) if low_lat_ms else None,
            "mean_ms": round(sum(low_lat_ms) / len(low_lat_ms), 3)
            if low_lat_ms else None,
            "deadline_ms": max_wait_ms,
            "bound_ms": round(low_bound_ms, 3),
        },
        "checks": {
            "speedup_ge_5x": bool(speedup >= 5.0),
            "hot_reload_zero_dropped": bool(
                server.reload_count >= min(1, reloads)
                and sum(other_errors) == 0
                and sum(shed_errors) == 0
            ),
            "p99_bounded": bool(p99_ms <= p99_bound_ms),
            "low_qps_bounded": bool(
                not low_lat_ms or max(low_lat_ms) <= low_bound_ms
            ),
        },
    }
    return result


def _socket_clients(host, port, clients, duration, obs_shape, think_ms,
                    seed, stop_evt=None, act_timeout=30.0):
    """Closed-loop ServingClient threads; returns per-client result dicts
    and the merged latency list (ms).  A request only counts dropped when
    its deadline expires unanswered (timeouts) — reconnect/retry churn is
    the transport's job and is counted, not failed."""
    import numpy as np

    from ape_x_dqn_tpu.serving import ServerOverloaded, ServingClient

    stop = stop_evt or threading.Event()
    results = [None] * clients

    def client(i: int) -> None:
        crng = np.random.default_rng(seed + 1000 + i)
        c = ServingClient(host, port, seed=seed + i)
        lat_ms: list = []
        ok = shed = timeouts = errors = 0
        while not stop.is_set():
            obs = crng.integers(0, 255, obs_shape, dtype=np.uint8)
            try:
                r = c.act(obs, timeout=act_timeout)
                ok += 1
                lat_ms.append(r.latency_s * 1e3)
            except ServerOverloaded:
                shed += 1
                time.sleep(0.005)
            except TimeoutError:
                timeouts += 1
            except Exception:  # noqa: BLE001 — counted, loop continues
                errors += 1
            if think_ms > 0:
                time.sleep(think_ms / 1e3)
        results[i] = {
            "requests": ok, "shed": shed, "timeouts": timeouts,
            "errors": errors, "retries": c.retries,
            "reconnects": c.reconnects,
            "mean_ms": round(sum(lat_ms) / len(lat_ms), 3) if lat_ms
            else None,
            "max_ms": round(max(lat_ms), 3) if lat_ms else None,
            # The per-client series, downsampled to <= 500 points so the
            # artifact stays readable (every k-th latency, order kept).
            "latency_series_ms": [
                round(v, 3)
                for v in lat_ms[::max(1, len(lat_ms) // 500)]
            ],
        }
        c.close()

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    if stop_evt is None:
        time.sleep(duration)
        stop.set()
    for t in threads:
        t.join(timeout=act_timeout + 30.0)
    wall = time.perf_counter() - t0
    done = [r for r in results if r is not None]
    merged = [v for r in done for v in r["latency_series_ms"]]
    return done, merged, wall, stop


def _pct(values, q):
    import numpy as np

    return round(float(np.percentile(np.asarray(values), q)), 3) \
        if values else None


def run_socket_loadgen(
    replicas: int = 2,
    clients: int = 8,
    duration: float = 6.0,
    think_ms: float = 0.0,
    network: str = "conv",
    env_name: str = "random:84x84x1",
    max_batch: int = 32,
    max_wait_ms: float = 5.0,
    queue_capacity: int = 256,
    reloads: int = 2,
    kill_replica_at: float = None,
    kill_rid: int = 0,
    seed: int = 0,
    warm_s: float = 1.5,
    spawn_timeout_s: float = 300.0,
) -> dict:
    """One fleet width, measured: spawn the fleet, publish, drive it in
    closed loop over sockets, hot-reload ``reloads`` times mid-window
    (perturbed params — real dirty pages, so pushes are delta-sized),
    optionally SIGKILL a replica mid-window, and tear down."""
    import jax
    import numpy as np

    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.components import build_components
    from ape_x_dqn_tpu.serving import ServingFleet

    overrides = [
        f"network={network}", f"env.name={env_name}",
        f"serving.max_batch={max_batch}",
        f"serving.max_wait_ms={max_wait_ms}",
        f"serving.queue_capacity={queue_capacity}",
        f"seed={seed}",
    ]
    cfg = ApexConfig()
    from ape_x_dqn_tpu.config import apply_overrides

    apply_overrides(cfg, overrides)
    cfg.validate()
    comps = build_components(cfg)
    obs_shape = comps.obs_shape

    events: list = []
    fleet = ServingFleet(
        replicas=replicas, probe_interval_s=cfg.serving.probe_interval_s,
        replica_args=[a for ov in overrides for a in ("--set", ov)],
        on_event=lambda kind, **f: events.append({"event": kind, **f}),
    )
    params = jax.tree_util.tree_map(
        np.array, jax.device_get(comps.state.params)
    )
    fleet.publish(params)
    result: dict = {
        "config": {
            "replicas": replicas, "clients": clients,
            "duration_s": duration, "think_ms": think_ms,
            "network": network, "env": env_name,
            "obs_shape": list(obs_shape), "max_batch": max_batch,
            "max_wait_ms": max_wait_ms, "reloads": reloads,
            "kill_replica_at": kill_replica_at,
        },
    }
    try:
        fleet.start(timeout=spawn_timeout_s)
        # Warm the path (router conns, first buckets) outside the clock.
        _socket_clients("127.0.0.1", fleet.port, min(2, clients), warm_s,
                        obs_shape, 0.0, seed + 7)

        stop = threading.Event()
        pushes: list = []

        def perturb_and_publish(r: int) -> None:
            # Scale + shift ONE leaf: real dirty pages (a bias init'd to
            # zeros would make ×-perturbation a no-op delta), a small
            # fraction of the snapshot — the delta-sized-push regime.
            leaves = jax.tree_util.tree_leaves(params)
            leaf = leaves[(r + 1) % len(leaves)]
            leaf += np.float32(1e-3) * (r + 1)
            pushes.append(fleet.publish(params))

        def driver() -> None:
            t0 = time.monotonic()
            fired_kill = kill_replica_at is None
            fired_reloads = 0
            while not stop.is_set():
                el = time.monotonic() - t0
                if el >= duration:
                    stop.set()
                    break
                if not fired_kill and el >= kill_replica_at:
                    fired_kill = True
                    result["killed_pid"] = fleet.replicas[kill_rid].pid
                    fleet.replicas[kill_rid].kill()
                if fired_reloads < reloads and \
                        el >= (fired_reloads + 1) * duration / (reloads + 1):
                    fired_reloads += 1
                    perturb_and_publish(fired_reloads)
                time.sleep(0.02)

        drv = threading.Thread(target=driver, daemon=True)
        drv.start()
        per_client, merged, wall, _ = _socket_clients(
            "127.0.0.1", fleet.port, clients, duration, obs_shape,
            think_ms, seed, stop_evt=stop,
        )
        drv.join(timeout=5.0)

        requests = sum(r["requests"] for r in per_client)

        def scrape_pv() -> dict:
            return {
                str(rid): ((v or {}).get("serving") or {})
                .get("param_version")
                for rid, v in fleet.replica_varz().items()
            }

        replica_pv = scrape_pv()
        if kill_replica_at is not None:
            # Fault run: let the respawn settle (bounded) before the
            # final scrape — "fresh param_version on every replica"
            # measures CONVERGENCE (full sync on reconnect), not
            # whether the window ended mid-boot.
            settle_deadline = time.monotonic() + 120.0
            while time.monotonic() < settle_deadline:
                if all(v == fleet.param_version
                       for v in replica_pv.values()):
                    break
                time.sleep(0.25)
                replica_pv = scrape_pv()
        st = fleet.stats()
        full_bytes = len(
            __import__(
                "ape_x_dqn_tpu.utils.serialization",
                fromlist=["tree_to_bytes"],
            ).tree_to_bytes(params)
        )
        delta_pushes = [p for p in pushes if p["delta"] > 0]
        result.update({
            "qps": round(requests / wall, 1),
            "requests": requests,
            "seconds": round(wall, 2),
            "latency": {
                "count": len(merged),
                "p50_ms": _pct(merged, 50),
                "p95_ms": _pct(merged, 95),
                "p99_ms": _pct(merged, 99),
                "max_ms": round(max(merged), 3) if merged else None,
            },
            "shed": sum(r["shed"] for r in per_client),
            "timeouts": sum(r["timeouts"] for r in per_client),
            "errors": sum(r["errors"] for r in per_client),
            "retries": sum(r["retries"] for r in per_client),
            "reconnects": sum(r["reconnects"] for r in per_client),
            "per_client": per_client,
            "reload_pushes": pushes,
            "param_full_bytes": full_bytes,
            "delta_bytes_max": max(
                (p["delta_bytes"] for p in delta_pushes), default=None
            ),
            "router": st["router"],
            "param": st["param"],
            "respawns": st["respawns"],
            "replica_param_version": replica_pv,
            "events": events[-64:],
            "checks": {
                "zero_drops": bool(
                    sum(r["timeouts"] + r["errors"] for r in per_client)
                    == 0
                ),
                "reloads_delta_sized": bool(
                    len(delta_pushes) == len(pushes) and pushes
                    and all(p["delta_bytes"] < full_bytes / 10
                            for p in delta_pushes)
                ),
                "all_replicas_fresh": bool(
                    replica_pv
                    and all(v == fleet.param_version
                            for v in replica_pv.values())
                ),
            },
        })
    finally:
        fleet.stop()
    return result


def run_socket_compare(replica_counts=(1, 2), **kw) -> dict:
    """The scale-out artifact: one fleet per width at MATCHED PER-REPLICA
    offered load (``clients`` closed-loop clients per replica) — the
    standard capacity-scaling measurement: each replica carries the same
    load it sustained alone, so N replicas sustaining N× the aggregate
    QPS at a pinned p99 is the horizontal claim.  (Fixed TOTAL load
    cannot show scale-out in closed loop unless latency falls — and on a
    single-core CI host two CPU-bound replicas only contend.)

    Fault injection (``kill_replica_at``) only fires on multi-replica
    widths — killing the only replica measures respawn, not routing."""
    kill_at = kw.pop("kill_replica_at", None)
    per_replica_clients = kw.pop("clients", 4)
    runs = {}
    for n in replica_counts:
        runs[f"replicas_{n}"] = run_socket_loadgen(
            replicas=n,
            clients=n * per_replica_clients,
            kill_replica_at=(kill_at if n > 1 else None),
            **kw,
        )
    ns = sorted(replica_counts)
    base, top = runs[f"replicas_{ns[0]}"], runs[f"replicas_{ns[-1]}"]
    p99s = [base["latency"]["p99_ms"], top["latency"]["p99_ms"]]
    out = {
        "methodology": (
            f"matched per-replica offered load: {per_replica_clients} "
            "closed-loop clients PER replica; aggregate QPS and p99 "
            "across fleet widths"
        ),
        "runs": runs,
        "scaleout": {
            "replicas": [ns[0], ns[-1]],
            "clients": [ns[0] * per_replica_clients,
                        ns[-1] * per_replica_clients],
            "qps": [base["qps"], top["qps"]],
            "speedup": round(top["qps"] / max(base["qps"], 1e-9), 3),
            "p99_ms": p99s,
        },
        "checks": {
            "scaleout_qps_higher": bool(top["qps"] > base["qps"]),
            # p99 pinned: the wider fleet holds the per-replica SLO
            # (generous 2.5x margin for a contended 1-core CI host).
            "p99_pinned": bool(
                p99s[0] is not None and p99s[1] is not None
                and p99s[1] <= 2.5 * p99s[0]
            ),
            "zero_drops_all": bool(
                all(r["checks"]["zero_drops"] for r in runs.values())
            ),
            "reloads_delta_sized_all": bool(
                all(r["checks"]["reloads_delta_sized"]
                    for r in runs.values())
            ),
            "all_replicas_fresh": bool(
                all(r["checks"]["all_replicas_fresh"]
                    for r in runs.values())
            ),
        },
    }
    return out


def parse_schedule(spec: str):
    """``"0:20,10:80,25:10"`` → [(t_offset_s, target_qps), ...] — a step
    schedule: the target holds from its offset until the next entry."""
    steps = []
    for item in spec.split(","):
        t, qps = item.split(":", 1)
        steps.append((float(t), float(qps)))
    steps.sort()
    if not steps or steps[0][0] > 0:
        steps.insert(0, (0.0, steps[0][1] if steps else 0.0))
    return steps


def _schedule_target(steps, elapsed: float) -> float:
    qps = steps[0][1]
    for t, q in steps:
        if elapsed >= t:
            qps = q
        else:
            break
    return qps


def run_schedule_loadgen(
    host: str,
    port: int,
    schedule,
    *,
    clients: int = 8,
    duration: float = 30.0,
    obs_shape=(84, 84, 1),
    seed: int = 0,
    tick_s: float = 1.0,
    act_timeout: float = 30.0,
    jsonl_path: str = None,
    stop_evt=None,
    conn_ttl_s: float = 0.0,
) -> dict:
    """Time-varying load (``--schedule``): PACED clients drive a step
    schedule of target QPS over real sockets — the disturbance source
    the elastic autopilot is tested against (ROADMAP item 3).

    Pacing: each of ``clients`` threads owes one request every
    ``clients / target_qps`` seconds against its own due-clock; when the
    service can't keep up the due-clock forgives debt beyond one
    interval (bounded burstiness — offered load tracks the schedule,
    it does not snowball).  A per-``tick_s`` collector computes the
    achieved QPS and windowed latency percentiles, tagged with the
    schedule phase — the ``series``; per-phase aggregates land in
    ``phases``; with ``jsonl_path`` each tick is also appended as one
    JSONL record (``event=loadgen_tick``).  A request counts DROPPED
    only when its deadline expires unanswered — reconnect/retry churn is
    the transport's job and is counted, not failed.

    ``conn_ttl_s`` > 0 makes each client recycle its connection on that
    cadence: the router balances at CONNECTION granularity, so churn is
    what lets a freshly scaled-up replica take its share of an
    already-connected fleet (production load balancers rely on the same
    property)."""
    import numpy as np

    from ape_x_dqn_tpu.serving import ServerOverloaded, ServingClient

    steps = (parse_schedule(schedule) if isinstance(schedule, str)
             else sorted(schedule))
    stop = stop_evt or threading.Event()
    lock = threading.Lock()
    samples: list = []          # (t_done_rel, lat_ms, kind)
    counts = {"requests": 0, "shed": 0, "timeouts": 0, "errors": 0,
              "retries": 0, "reconnects": 0}
    t0 = time.monotonic()

    def client(i: int) -> None:
        crng = np.random.default_rng(seed + 1000 + i)
        c = ServingClient(host, port, seed=seed + i)
        conn_born = time.monotonic()
        due = t0 + (i / max(1, clients)) * 1.0   # spread the first wave
        while not stop.is_set():
            now = time.monotonic()
            if conn_ttl_s > 0 and now - conn_born > conn_ttl_s:
                with lock:
                    counts["retries"] += c.retries
                    counts["reconnects"] += c.reconnects
                c.close()
                c = ServingClient(host, port, seed=seed + i)
                conn_born = now
            if now < due:
                if stop.wait(min(due - now, 0.25)):
                    break
                continue
            el = now - t0
            target = _schedule_target(steps, el)
            interval = clients / max(target, 1e-3)
            obs = crng.integers(0, 255, obs_shape, dtype=np.uint8)
            kind = "ok"
            lat_ms = None
            try:
                r = c.act(obs, timeout=act_timeout)
                lat_ms = r.latency_s * 1e3
            except ServerOverloaded:
                kind = "shed"
            except TimeoutError:
                kind = "timeout"
            except Exception:  # noqa: BLE001 — counted, loop continues
                kind = "error"
            done = time.monotonic()
            with lock:
                if kind == "ok":
                    counts["requests"] += 1
                    samples.append((done - t0, lat_ms, kind))
                else:
                    counts[{"shed": "shed", "timeout": "timeouts",
                            "error": "errors"}[kind]] += 1
            # Bounded debt: fall at most one interval behind schedule.
            due = max(due + interval, done - interval)
        with lock:
            counts["retries"] += c.retries
            counts["reconnects"] += c.reconnects
        c.close()

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()

    series: list = []
    jsonl = open(jsonl_path, "a") if jsonl_path else None
    tick_start = 0.0
    consumed = 0
    try:
        while not stop.is_set():
            el = time.monotonic() - t0
            if el >= duration:
                stop.set()
                break
            stop.wait(min(tick_s, duration - el))
            now_rel = time.monotonic() - t0
            with lock:
                window = samples[consumed:]
                consumed = len(samples)
                snap = dict(counts)
            lat = [s[1] for s in window]
            phase = sum(1 for t_, _ in steps if t_ <= tick_start) - 1
            rec = {
                "t": round(tick_start, 2),
                "phase": phase,
                "target_qps": _schedule_target(steps, tick_start),
                "qps": round(len(window) / max(now_rel - tick_start,
                                               1e-6), 2),
                "p50_ms": _pct(lat, 50),
                "p99_ms": _pct(lat, 99),
                "requests": snap["requests"],
                "shed": snap["shed"],
                "timeouts": snap["timeouts"],
                "errors": snap["errors"],
            }
            series.append(rec)
            if jsonl is not None:
                jsonl.write(json.dumps(
                    {"event": "loadgen_tick", **rec}) + "\n")
                jsonl.flush()
            tick_start = now_rel
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=act_timeout + 10.0)
        if jsonl is not None:
            jsonl.close()

    phases: list = []
    for pi, (pt, pq) in enumerate(steps):
        ticks = [r for r in series if r["phase"] == pi]
        if not ticks:
            continue
        with lock:
            p_lat = [s[1] for s in samples
                     if pt <= s[0] < (steps[pi + 1][0]
                                      if pi + 1 < len(steps)
                                      else float("inf"))]
        phases.append({
            "phase": pi,
            "t0": pt,
            "target_qps": pq,
            "ticks": len(ticks),
            "qps_mean": round(sum(r["qps"] for r in ticks)
                              / len(ticks), 2),
            "p50_ms": _pct(p_lat, 50),
            "p95_ms": _pct(p_lat, 95),
            "p99_ms": _pct(p_lat, 99),
            "max_ms": round(max(p_lat), 3) if p_lat else None,
        })
    with lock:
        final = dict(counts)
    return {
        "config": {"connect": f"{host}:{port}", "clients": clients,
                   "duration_s": duration, "tick_s": tick_s,
                   "obs_shape": list(obs_shape)},
        "schedule": [[t, q] for t, q in steps],
        "series": series,
        "phases": phases,
        **final,
        "checks": {
            "zero_drops": bool(final["timeouts"] + final["errors"] == 0),
        },
    }


def run_connect_loadgen(host: str, port: int, clients: int,
                        duration: float, obs_shape, think_ms: float,
                        seed: int) -> dict:
    """Clients-only mode against an external fleet/replica."""
    per_client, merged, wall, _ = _socket_clients(
        host, port, clients, duration, obs_shape, think_ms, seed
    )
    requests = sum(r["requests"] for r in per_client)
    return {
        "config": {"connect": f"{host}:{port}", "clients": clients,
                   "duration_s": duration, "think_ms": think_ms,
                   "obs_shape": list(obs_shape)},
        "qps": round(requests / wall, 1),
        "requests": requests,
        "seconds": round(wall, 2),
        "latency": {
            "count": len(merged),
            "p50_ms": _pct(merged, 50),
            "p95_ms": _pct(merged, 95),
            "p99_ms": _pct(merged, 99),
        },
        "shed": sum(r["shed"] for r in per_client),
        "timeouts": sum(r["timeouts"] for r in per_client),
        "errors": sum(r["errors"] for r in per_client),
        "per_client": per_client,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--clients", type=int, default=32)
    p.add_argument("--duration", type=float, default=6.0)
    p.add_argument("--think-ms", type=float, default=0.0)
    p.add_argument("--network", default="conv",
                   choices=("conv", "nature", "mlp"))
    p.add_argument("--obs", default="84x84x1", help="observation shape HxWxC")
    p.add_argument("--num-actions", type=int, default=4)
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--queue-capacity", type=int, default=256)
    p.add_argument("--seq-seconds", type=float, default=3.0)
    p.add_argument("--reloads", type=int, default=2)
    p.add_argument("--low-qps-requests", type=int, default=20)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--platform", default=None,
        help="force a jax platform (e.g. 'cpu') BEFORE backend init — how "
        "a parent that owns the chip runs this beside itself; "
        "socket modes that spawn their own fleet default to 'cpu'",
    )
    p.add_argument("--out", default=None, help="write the result JSON here")
    # -- socket mode (ISSUE 9) --------------------------------------------
    p.add_argument(
        "--serve-replicas", type=int, default=None, metavar="N",
        help="socket mode: spawn an N-replica routed fleet and drive it "
        "over real sockets (closed-loop ServingClient threads)",
    )
    p.add_argument(
        "--compare-replicas", default=None, metavar="N1,N2",
        help="socket mode: one fleet per width, matched total load — the "
        "scale-out artifact (demos/serving_net.json)",
    )
    p.add_argument(
        "--connect", default=None, metavar="HOST:PORT",
        help="socket mode: clients only, against an external fleet",
    )
    p.add_argument(
        "--kill-replica-at", type=float, default=None, metavar="SEC",
        help="SIGKILL one replica this many seconds into the measured "
        "window (router-recovery fault toggle; multi-replica fleets only)",
    )
    p.add_argument("--kill-rid", type=int, default=0,
                   help="which replica --kill-replica-at kills")
    p.add_argument("--env", default="random:84x84x1",
                   help="replica env spec (fixes obs shape + num_actions)")
    p.add_argument("--warm-s", type=float, default=1.5,
                   help="socket-mode warmup seconds outside the clock")
    p.add_argument(
        "--schedule", default=None, metavar="T:QPS,T:QPS,...",
        help="time-varying load: a step schedule of target QPS over the "
        "run (paced clients; per-phase/per-tick series on the output) — "
        "pairs with --connect or --serve-replicas; --duration still "
        "bounds the whole run",
    )
    p.add_argument("--schedule-jsonl", default=None, metavar="PATH",
                   help="append one loadgen_tick JSONL record per tick")
    p.add_argument("--tick-s", type=float, default=1.0,
                   help="schedule-mode collector tick")
    p.add_argument("--conn-ttl-s", type=float, default=0.0,
                   help="schedule-mode connection recycle cadence (0 = "
                   "persistent connections; churn lets a scaled-up "
                   "replica take load from connected clients)")
    args = p.parse_args(argv)

    if args.platform is None and (
        args.serve_replicas is not None or args.compare_replicas
    ):
        # A fleet parent does no inference: it builds a params template and
        # spawns CPU replicas, so it never needs (or takes) a chip.
        args.platform = "cpu"
    if args.platform:
        # Before the first jax import (run_loadgen does the jax imports).
        os.environ["JAX_PLATFORMS"] = args.platform

    socket_kw = dict(
        clients=args.clients,
        duration=args.duration,
        think_ms=args.think_ms,
        network=args.network,
        env_name=args.env,
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        queue_capacity=args.queue_capacity,
        reloads=args.reloads,
        seed=args.seed,
        warm_s=args.warm_s,
    )
    if args.schedule and args.connect:
        host, port = args.connect.rsplit(":", 1)
        result = run_schedule_loadgen(
            host or "127.0.0.1", int(port), args.schedule,
            clients=args.clients, duration=args.duration,
            obs_shape=_parse_obs(args.obs), seed=args.seed,
            tick_s=args.tick_s, jsonl_path=args.schedule_jsonl,
            conn_ttl_s=args.conn_ttl_s,
        )
    elif args.schedule and args.serve_replicas:
        # Spawn the routed fleet, then drive the schedule through it.
        import jax
        import numpy as np

        from ape_x_dqn_tpu.config import ApexConfig, apply_overrides
        from ape_x_dqn_tpu.runtime.components import build_components
        from ape_x_dqn_tpu.serving import ServingFleet

        cfg = apply_overrides(ApexConfig(), [
            f"network={args.network}", f"env.name={args.env}",
        ])
        comps = build_components(cfg)
        fleet = ServingFleet(
            replicas=args.serve_replicas,
            replica_args=["--set", f"network={args.network}",
                          "--set", f"env.name={args.env}"],
        )
        fleet.publish(jax.tree_util.tree_map(
            np.array, jax.device_get(comps.state.params)))
        try:
            fleet.start()
            result = run_schedule_loadgen(
                "127.0.0.1", fleet.port, args.schedule,
                clients=args.clients, duration=args.duration,
                obs_shape=comps.obs_shape, seed=args.seed,
                tick_s=args.tick_s, jsonl_path=args.schedule_jsonl,
                conn_ttl_s=args.conn_ttl_s,
            )
        finally:
            fleet.stop()
    elif args.compare_replicas:
        counts = tuple(int(x) for x in args.compare_replicas.split(","))
        result = run_socket_compare(
            counts, kill_replica_at=args.kill_replica_at, **socket_kw
        )
    elif args.serve_replicas:
        result = run_socket_loadgen(
            replicas=args.serve_replicas,
            kill_replica_at=args.kill_replica_at,
            kill_rid=args.kill_rid, **socket_kw,
        )
    elif args.connect:
        host, port = args.connect.rsplit(":", 1)
        result = run_connect_loadgen(
            host or "127.0.0.1", int(port), args.clients, args.duration,
            _parse_obs(args.obs), args.think_ms, args.seed,
        )
    else:
        result = run_loadgen(
            clients=args.clients,
            duration=args.duration,
            think_ms=args.think_ms,
            network=args.network,
            obs_shape=_parse_obs(args.obs),
            num_actions=args.num_actions,
            max_batch=args.max_batch,
            max_wait_ms=args.max_wait_ms,
            queue_capacity=args.queue_capacity,
            seq_seconds=args.seq_seconds,
            reloads=args.reloads,
            low_qps_requests=args.low_qps_requests,
            seed=args.seed,
        )
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
