"""CLI serving mode: ``python -m ape_x_dqn_tpu.serve``.

Mounting modes for the same PolicyServer (serving/server.py), one per
param source:

  * ``--checkpoint DIR`` — serve a trained Q-network from a checkpoint
    root, hot-reloading whenever a newer committed ``step_N`` lands;
  * ``--attach`` — run the async trainer (runtime/async_pipeline.py) in
    this process and serve from its LIVE ParamStore;
  * ``--param-hub host:port:token:rid:attempt`` — REPLICA mode: subscribe
    to a fleet's param hub over a socket (serving/sources.py
    ``SocketParamSource``) — full snapshot on connect, page-deltas after;
  * ``--param-tail DIR`` — tail a ``ParamTailWriter`` APXC delta-chunk
    chain on a shared filesystem (the checkpoint-attached fallback:
    delta-sized files instead of full checkpoint re-reads).

Orthogonally, ``--listen [HOST:]PORT`` mounts the socket front end
(serving/net_server.py) over whichever server the mode built, announcing
the bound port as a ``serving_listen`` JSONL event (what the router and
the CI gates parse; port 0 = ephemeral).  ``--duration 0`` serves until
SIGTERM/SIGINT — how replicas run under a fleet.

``--replicas N`` is FLEET mode: spawn N replica subprocesses (each
``--listen <host>:0 --param-hub …``), route client connections to them
health-aware (serving/router.py), watch ``--checkpoint`` for new steps
and fan each one out to every replica as delta-or-full framed messages —
a hot reload reaches the whole fleet without any replica touching the
checkpoint dir.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time

from ape_x_dqn_tpu.config import load_config, to_dict
from ape_x_dqn_tpu.utils.metrics import MetricLogger


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ape_x_dqn_tpu.serve",
        description="Batched Q-network policy serving with hot param "
        "reload, a socket front end, and an N-replica routed fleet",
    )
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument(
        "--checkpoint", default=None, metavar="DIR",
        help="serve from this checkpoint root (hot-reloads newer steps); "
        "with --replicas: watch it and fan new steps out to the fleet",
    )
    src.add_argument(
        "--attach", action="store_true",
        help="run the async trainer in-process and serve its live params",
    )
    src.add_argument(
        "--param-hub", default=None, metavar="HOST:PORT:TOKEN:RID:ATTEMPT",
        help="replica mode: subscribe to a fleet param hub over a socket "
        "(delta-or-full framed updates; full snapshot on connect)",
    )
    src.add_argument(
        "--param-tail", default=None, metavar="DIR",
        help="tail a ParamTailWriter APXC delta-chunk chain in DIR",
    )
    p.add_argument(
        "--listen", default=None, metavar="[HOST:]PORT",
        help="serve the socket request/reply protocol here (0 = ephemeral; "
        "the bound port is announced as a serving_listen JSONL event)",
    )
    p.add_argument(
        "--replicas", type=int, default=None, metavar="N",
        help="fleet mode: N replica subprocesses behind the health-aware "
        "router (requires --checkpoint; 0 = serving.replicas default)",
    )
    p.add_argument(
        "--run-token", type=int, default=0, metavar="TOKEN",
        help="fleet-internal serving token: v2 hellos (central-inference "
        "workers) must carry it or are rejected at the handshake; 0 "
        "accepts any hello (anonymous front door)",
    )
    p.add_argument(
        "--params-file", default=None,
        help="JSON config (native or reference format) — must match the "
        "checkpoint's network/env for --checkpoint",
    )
    p.add_argument(
        "--set", dest="overrides", action="append", default=[],
        metavar="PATH=VALUE",
        help="config override, e.g. --set serving.max_batch=64",
    )
    p.add_argument(
        "--duration", type=float, default=10.0,
        help="seconds to serve; 0 = until SIGTERM/SIGINT (replica mode)",
    )
    p.add_argument(
        "--clients", type=int, default=0,
        help="built-in closed-loop demo clients (0 = idle serve)",
    )
    p.add_argument(
        "--steps", type=int, default=None,
        help="--attach: learner steps to train (default: config total)",
    )
    p.add_argument("--metrics-file", default=None, help="also write JSONL here")
    p.add_argument("--metrics-every", type=float, default=2.0)
    p.add_argument(
        "--obs-port", type=int, default=None, metavar="PORT",
        help="start the /metrics + /varz + /healthz exporter on this port "
        "(0 = ephemeral; overrides config obs.export_port)",
    )
    return p


def _parse_listen(spec: str, default_host: str):
    """``[HOST:]PORT`` → (host, port)."""
    if ":" in spec:
        host, port = spec.rsplit(":", 1)
        return host or default_host, int(port)
    return default_host, int(spec)


def _install_stop_handlers(stop: threading.Event) -> None:
    """SIGTERM/SIGINT → clean drain: the fleet stops replicas with
    SIGTERM, and a replica must close its sockets and flush its final
    metrics record instead of dying mid-frame."""

    def _handler(signum, frame):  # noqa: ARG001
        stop.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _handler)
        except (ValueError, OSError):
            pass  # non-main thread (tests drive main() directly)


def _client_loop(server, obs_shape, stop, errors, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    while not stop.is_set():
        obs = rng.integers(0, 255, obs_shape, dtype=np.uint8)
        try:
            server.act(obs, timeout=30.0)
        except Exception:  # noqa: BLE001 — counted, loop continues
            errors.append(1)


def _run_fleet(args, cfg, logger) -> int:
    """--replicas N: router + param hub + N replica children, watching
    the checkpoint dir and fanning new steps out as deltas."""
    # The fleet parent does no inference — it owns a params template, the
    # router and the hub.  Keep it off the chip (a trainer beside it may
    # hold it): assigned before this process first imports jax.
    os.environ["JAX_PLATFORMS"] = "cpu"
    from ape_x_dqn_tpu.parallel.mesh import device_info
    from ape_x_dqn_tpu.runtime.components import build_components
    from ape_x_dqn_tpu.serving import CheckpointParamSource, ServingFleet
    from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if not args.checkpoint:
        print("--replicas requires --checkpoint (the fleet's param feed)",
              file=sys.stderr)
        return 2
    s = cfg.serving
    n = args.replicas if args.replicas and args.replicas > 0 else s.replicas
    comps = build_components(cfg)
    source = CheckpointParamSource(args.checkpoint, comps.state)
    got = source.get(-1)
    if got is None:
        print(f"no checkpoint under {args.checkpoint}", file=sys.stderr)
        return 2
    params, step = got

    host, port = (s.listen_host, s.listen_port)
    if args.listen is not None:
        host, port = _parse_listen(args.listen, s.listen_host)
    replica_args = []
    if args.params_file:
        replica_args += ["--params-file", args.params_file]
    for ov in args.overrides:
        replica_args += ["--set", ov]

    fleet = ServingFleet(
        replicas=n, listen_host=host, listen_port=port,
        probe_interval_s=s.probe_interval_s, replica_args=replica_args,
        on_event=lambda kind, **f: logger.event(kind, **f),
    )
    push = fleet.publish(params)
    logger.event("fleet_param_push", step=int(step), **push)
    try:
        fleet.start(timeout=s.replica_spawn_timeout_s)
    except Exception as e:  # noqa: BLE001 — spawn failure is terminal
        print(f"fleet start failed: {e}", file=sys.stderr)
        fleet.stop()
        return 3
    logger.event("serving_listen", port=fleet.port, host=host,
                 replicas=n, mode="router",
                 platform=device_info()["platform"])

    obs_server = None
    obs_port = args.obs_port if args.obs_port is not None \
        else cfg.obs.export_port
    if obs_port is not None:
        from ape_x_dqn_tpu.obs import Health, MetricsRegistry, ObsServer

        registry = MetricsRegistry()
        health = Health(stale_after_s=cfg.obs.heartbeat_stale_s)
        registry.register_provider(
            "serving_router", fleet.router.stats
        )
        registry.register_provider("serving_fleet", fleet.stats)
        health.register(
            "router",
            lambda: 0.0 if fleet.router.stats()["healthy"] > 0 else 1e9,
            stale_after_s=1.0,
        )
        obs_server = ObsServer(registry, health, port=obs_port)
        logger.event("obs_exporter", port=obs_server.port,
                     url=obs_server.url)

    stop = threading.Event()
    _install_stop_handlers(stop)
    have_step = int(step)
    try:
        deadline = (time.monotonic() + args.duration
                    if args.duration > 0 else None)
        next_emit = time.monotonic() + args.metrics_every
        while not stop.is_set():
            if deadline is not None and time.monotonic() >= deadline:
                break
            # Poll the checkpoint dir at the reload cadence; emit a
            # metrics record at the (coarser) metrics cadence.
            stop.wait(min(args.metrics_every, cfg.serving.reload_poll_s))
            got = source.get(have_step)
            if got is not None:
                params, have_step = got[0], int(got[1])
                push = fleet.publish(params)
                logger.event("fleet_param_push", step=have_step, **push)
            if time.monotonic() >= next_emit:
                next_emit = time.monotonic() + args.metrics_every
                st = fleet.stats()
                logger.emit(serving_router=st["router"],
                            serving_fleet={k: st[k] for k in
                                           ("param", "respawns", "spawned",
                                            "retires", "retired",
                                            "param_version", "replicas")})
    finally:
        st = fleet.stats()
        logger.emit(serving_router=st["router"],
                    serving_fleet={k: st[k] for k in
                                   ("param", "respawns", "spawned",
                                    "retires", "retired", "param_version",
                                    "replicas")},
                    final=True)
        fleet.stop()
        if obs_server is not None:
            obs_server.close()
        logger.close()
    return 0


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    cfg = load_config(args.params_file, overrides=args.overrides)
    print("serving config:", to_dict(cfg), file=sys.stderr)
    logger = MetricLogger(stream=sys.stdout, path=args.metrics_file)

    if args.replicas is not None:
        return _run_fleet(args, cfg, logger)

    from ape_x_dqn_tpu.parallel.mesh import device_info
    from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from ape_x_dqn_tpu.runtime.components import build_components
    from ape_x_dqn_tpu.serving import (
        CheckpointParamSource,
        ParamTailSource,
        PolicyServer,
        ServingNetServer,
        SocketParamSource,
    )

    pipe = None
    trainer_thread = None
    trainer_error: list = []
    if args.attach:
        # One process, both halves: the trainer owns the device hot loop,
        # the serving batcher rides the same device between learner
        # dispatches, params flow learner -> store -> server in host RAM.
        from ape_x_dqn_tpu.runtime import AsyncPipeline

        pipe = AsyncPipeline(cfg, logger=logger, log_every=10_000)
        comps = pipe.comps
        source = pipe.store

        def _train():
            try:
                pipe.run(learner_steps=args.steps)
            except BaseException as e:  # noqa: BLE001 — re-raised by main
                trainer_error.append(e)

        trainer_thread = threading.Thread(
            target=_train, name="attached-trainer", daemon=True,
        )
    else:
        comps = build_components(cfg)
        if args.param_hub:
            # Replica under a fleet: params arrive over the hub socket
            # (full on connect, deltas after) — no checkpoint dir here.
            source = SocketParamSource(args.param_hub, comps.state.params)
        elif args.param_tail:
            source = ParamTailSource(args.param_tail, comps.state.params)
            if source.version < 0:
                print(f"no param-tail chain under {args.param_tail}",
                      file=sys.stderr)
                return 2
        else:
            source = CheckpointParamSource(args.checkpoint, comps.state)
            if source.version < 0:
                print(f"no checkpoint under {args.checkpoint}",
                      file=sys.stderr)
                return 2

    s = cfg.serving
    server = PolicyServer(
        comps.network,
        param_source=source,
        max_batch=s.max_batch,
        max_wait_ms=s.max_wait_ms,
        queue_capacity=s.queue_capacity,
        reload_poll_s=s.reload_poll_s,
        # A replica may come up before its fleet's first publish reaches
        # it; give the socket source the spawn budget, not 30 s.
        source_timeout_s=(s.replica_spawn_timeout_s if args.param_hub
                          else 30.0),
        # Chaos: seeded per-batch service delay (the serving twin of the
        # slow-env injector — the autopilot smoke's disturbance source).
        apply_delay_ms=(cfg.chaos.serving_delay_ms
                        if cfg.chaos.enabled else 0.0),
        delay_seed=cfg.chaos.seed,
    )
    server.warmup(comps.obs_shape)
    server.start()

    # Socket front end: the request/reply plane over this server's
    # batcher.  The bound port is announced on the JSONL stream — the
    # router (fleet mode) and CI gates parse the serving_listen event.
    net_srv = None
    if args.listen is not None:
        host, port = _parse_listen(args.listen, s.listen_host)
        net_srv = ServingNetServer(
            server, host=host, port=port,
            max_request_bytes=s.max_request_bytes,
            run_token=args.run_token,
        ).start()
        server.attach_transport(net_srv.stats)
        device = device_info()
        logger.event("serving_listen", port=net_srv.port, host=host,
                     mode="replica", platform=device["platform"],
                     device_kind=device["device_kind"])

    # Serving staleness policy (runtime/supervisor): past
    # serving.param_stale_s of source silence the server sheds with the
    # typed ServerOverloaded and /healthz goes 503 — stale answers from a
    # dead source are a failure mode, not a feature.  Under --attach the
    # trainer's FleetSupervisor ticks it; standalone the metrics loop does.
    staleness = None
    if cfg.serving.param_stale_s > 0:
        if pipe is not None and pipe.supervisor is not None:
            staleness = pipe.supervisor.attach_serving(
                server, cfg.serving.param_stale_s
            )
        else:
            from ape_x_dqn_tpu.runtime.supervisor import (
                ServingStalenessPolicy,
            )

            staleness = ServingStalenessPolicy(
                server, cfg.serving.param_stale_s,
                on_event=lambda kind, **f: logger.event(kind, **f),
            )

    # Observability exporter over the serving tier (and, under --attach,
    # the trainer's registry too — one scrape covers both halves).
    obs_server = None
    obs_port = args.obs_port if args.obs_port is not None \
        else cfg.obs.export_port
    if obs_port is not None:
        from ape_x_dqn_tpu.obs import Health, MetricsRegistry, ObsServer

        if pipe is not None:
            registry, health = pipe.obs_registry, pipe.health
            pipe._close_obs()  # serve.py's exporter owns the port here
        else:
            registry = MetricsRegistry()
            health = Health(stale_after_s=cfg.obs.heartbeat_stale_s)
        registry.register_provider("serving", server.stats)
        health.register(
            "serving_batcher",
            lambda: time.monotonic() - server.batcher.heartbeat,
        )
        if staleness is not None:
            health.register(
                "serving_params", staleness.age_s,
                stale_after_s=cfg.serving.param_stale_s,
            )
        obs_server = ObsServer(registry, health, port=obs_port)
        logger.event("obs_exporter", port=obs_server.port,
                     url=obs_server.url)

    if pipe is not None and net_srv is not None:
        # The attached trainer's periodic JSONL records carry the socket
        # plane as their own section (docs/METRICS.md `serving_net`).
        pipe.register_jsonl_section("serving_net", net_srv.stats)

    if trainer_thread is not None:
        trainer_thread.start()

    stop = threading.Event()
    _install_stop_handlers(stop)
    errors: list = []
    clients = [
        threading.Thread(
            target=_client_loop,
            args=(server, comps.obs_shape, stop, errors, cfg.seed + i),
            name=f"serve-client-{i}", daemon=True,
        )
        for i in range(args.clients)
    ]
    for c in clients:
        c.start()
    try:
        deadline = (time.monotonic() + args.duration
                    if args.duration > 0 else None)
        while not stop.is_set():
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                stop.wait(min(args.metrics_every, remaining))
            else:
                stop.wait(args.metrics_every)
            if staleness is not None:
                staleness.check()
            extra = {"serving_net": net_srv.stats()} if net_srv else {}
            server.emit_metrics(logger, **extra)
            if trainer_thread is not None and not trainer_thread.is_alive():
                break
    finally:
        stop.set()
        for c in clients:
            c.join(timeout=5.0)
        if pipe is not None:
            pipe.stop_event.set()
        if trainer_thread is not None and trainer_thread.is_alive():
            trainer_thread.join(timeout=30.0)
        if net_srv is not None:
            net_srv.close()
        extra = {"serving_net": net_srv.stats()} if net_srv else {}
        server.emit_metrics(logger, final=True, **extra)
        if obs_server is not None:
            obs_server.close()
        server.close()
        if hasattr(source, "close"):
            source.close()
        logger.close()
    if trainer_error:
        # A dead trainer is a failed run, not a clean end of serving.
        raise RuntimeError("attached trainer died") from trainer_error[0]
    return 0 if not errors else 1


if __name__ == "__main__":
    raise SystemExit(main())
