#!/usr/bin/env python
"""Elastic-autopilot smoke gate (tools/verify_t1.sh gate 12).

ROADMAP item 3's done-condition, CI-sized, on real processes: a mid-run
load change on EACH fleet absorbed by the capacity controller with the
target SLO metric re-held, zero dropped requests during serving
scale-down, and the controller provably idle while all SLOs are green.

  1. an in-process trainer (AsyncPipeline: process actors under
     ``chaos.env_latency_ms`` slow envs, host replay, autopilot ENABLED
     with the in-process FleetAggregator sensor) runs next to a
     1-replica ServingFleet whose replicas carry
     ``chaos.serving_delay_ms`` — service time is SLEEP-bound, so
     replica capacity genuinely scales on this 1-core host;
  2. ``tools/loadgen.py --schedule`` drives the serving tier through a
     step schedule (baseline → surge → idle) over real sockets with
     connection churn (the router balances connections);
  3. GREEN phase: with every rule measurable and green, the controller
     must decide NOTHING;
  4. serving surge: p99 breaches (burn-windowed) → the autopilot spawns
     replica 2 (``ServingFleet.spawn``; one step, then busy-hold) → the
     windowed p99 re-holds → ``slo_clear``;
  5. serving idle: per-replica QPS sits under the idle bound → the
     autopilot retires the extra replica on the zero-drop drain path
     (router ``remove_endpoint`` first, SIGTERM after the grace) — the
     loadgen must count ZERO timeouts/errors across the whole run;
  6. actor drill (kill-half-the-workers): wid 1 is SIGKILLed through
     its respawn until the supervisor QUARANTINES it — the fleet
     shrinks, age-of-experience p95 breaches — and the autopilot grows
     the reserved wid 2 (same ε-ladder partition) until the windowed
     age p95 re-holds → ``slo_clear``;
  7. the committed artifact (``demos/autopilot.json``) carries the
     action trail, the SLO event stream, the loadgen phase series, and
     an ``obs_top --fleet`` frame with the autopilot row.

    python tools/autopilot_smoke.py [--out demos/autopilot.json]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Schedule (seconds into the loadgen run : target QPS).
BASE_QPS = 8.0
SURGE_QPS = 28.0
IDLE_QPS = 4.0
T_SURGE = 35.0
T_IDLE = 80.0
DURATION = 165.0
SERVING_DELAY_MS = 50.0
P99_BOUND_MS = 450.0
AGE_BOUND_MS = 6500.0
IDLE_PER_REPLICA = 3.0


def _tail_jsonl(path):
    recs = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return recs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="autopilot_smoke")
    ap.add_argument("--out", default="-")
    ap.add_argument("--deadline", type=float, default=420.0)
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"  # before the first jax import
    import jax
    import numpy as np

    from ape_x_dqn_tpu.autopilot import ServingFleetActuator
    from ape_x_dqn_tpu.config import ApexConfig, apply_overrides
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.serving import ServingFleet
    from ape_x_dqn_tpu.utils.metrics import MetricLogger
    from tools.loadgen import run_schedule_loadgen
    from tools.obs_top import render_fleet

    t_start = time.monotonic()

    def remaining() -> float:
        return args.deadline - (time.monotonic() - t_start)

    tmp = tempfile.mkdtemp(prefix="autopilot-smoke-")
    trainer_log = os.path.join(tmp, "trainer.jsonl")
    verdict = {"ok": False}
    pipe = None
    fleet = None
    run_thread = None
    run_err: list = []
    ld_result: dict = {}
    ld_stop = threading.Event()
    try:
        cfg = apply_overrides(ApexConfig(), [
            "network=mlp", "env.name=chain:6", "seed=7",
            # Elastic process fleet: 2 spawned, 1 reserved wid of
            # headroom, 2 actors per slice on the global ladder.
            "actor.mode=process", "actor.num_workers=2",
            "actor.max_workers=3", "actor.num_actors=6",
            "actor.T=100000000", "actor.flush_every=8",
            "actor.sync_every=32",
            "learner.min_replay_mem_size=400",
            "learner.total_steps=100000000",
            "learner.optimizer=adam", "learner.learning_rate=0.001",
            "learner.publish_every=10",
            "replay.capacity=1024",
            # Slow envs from spawn: worker throughput is sleep-bound, so
            # fleet width genuinely moves age-of-experience.
            "chaos.enabled=true", "chaos.seed=7",
            "chaos.env_latency_ms=6",
            # Two SIGKILLs quarantine a worker (the kill-half drill).
            "supervisor.crash_loop_budget=1",
            "supervisor.crash_loop_window_s=90",
            # SLO rules + burn windows (scrape 0.5 s -> 16-sample window).
            "obs.fleet_scrape_interval_s=0.5",
            f"obs.fleet_slo_age_p95_ms={AGE_BOUND_MS}",
            f"obs.fleet_slo_serving_p99_ms={P99_BOUND_MS}",
            "obs.fleet_slo_endpoint_alive=false",
            "obs.fleet_slo_window_s=8",
            "obs.fleet_slo_burn_threshold=0.5",
            "obs.fleet_slo_clear_threshold=0.25",
            "obs.fleet_slo_min_samples=4",
            # The controller under test.
            "autopilot.enabled=true", "autopilot.poll_s=0.5",
            "autopilot.actor_min_workers=1",
            "autopilot.serving_min_replicas=1",
            "autopilot.serving_max_replicas=2",
            "autopilot.cooldown_up_s=10",
            "autopilot.cooldown_down_s=8",
            "autopilot.hold_opposite_s=6",
            f"autopilot.serving_idle_qps_per_replica={IDLE_PER_REPLICA}",
            "autopilot.idle_window_s=8",
            # Discovery plane: the trainer hosts the membership registry;
            # serving replicas ANNOUNCE themselves (fleet/registry.py) and
            # the aggregator adopts them from membership — no driver-side
            # endpoint polling anywhere in this smoke.
            "fleet.discovery=registry",
        ])
        logger = MetricLogger(path=trainer_log)
        pipe = AsyncPipeline(cfg, logger=logger, log_every=500)
        pool = pipe.worker.pool
        agg = pipe.autopilot_aggregator

        # -- serving fleet: 1 replica, sleep-bound service time --------
        # Registered with the trainer-hosted membership registry: every
        # replica that reaches rotation announces itself (varz_url in
        # the member doc) and the aggregator adopts it from membership —
        # an autopilot-spawned replica is discovered exactly like the
        # seed one, with no endpoint-sync polling in this driver.
        fleet = ServingFleet(
            replicas=1, probe_interval_s=0.5,
            on_event=lambda kind, **f: logger.event(kind, **f),
            registry_addr=("127.0.0.1", pipe.fleet_registry.port),
            registry_token=pipe.fleet_registry.token,
            heartbeat_s=0.5,
            replica_args=[
                "--set", "network=mlp", "--set", "env.name=chain:6",
                "--set", "serving.max_batch=1",
                "--set", "serving.max_wait_ms=1",
                "--set", "chaos.enabled=true",
                "--set", f"chaos.serving_delay_ms={SERVING_DELAY_MS}",
            ],
        )
        fleet.publish(jax.tree_util.tree_map(
            np.array, jax.device_get(pipe.comps.state.params)))
        fleet.start(timeout=min(240.0, remaining()))
        pipe.autopilot.attach_serving(
            ServingFleetActuator(fleet, drain_grace_s=2.0))

        # -- trainer thread + loadgen schedule -------------------------
        def _run():
            try:
                pipe.run(learner_steps=100_000_000, warmup_timeout=240.0)
            except BaseException as e:  # noqa: BLE001 — surfaced at verdict time
                if not pipe.stop_event.is_set():
                    run_err.append(f"{type(e).__name__}: {e}")

        run_thread = threading.Thread(target=_run, name="trainer",
                                      daemon=True)
        run_thread.start()

        def events(kind=None):
            recs = [r for r in _tail_jsonl(trainer_log) if "event" in r]
            if kind is None:
                return recs
            return [r for r in recs if r["event"] == kind]

        def actions(**match):
            out = []
            for r in events("autopilot_action"):
                if all(r.get(k) == v for k, v in match.items()):
                    out.append(r)
            return out

        def wait_for(cond, timeout, what):
            deadline = time.monotonic() + min(timeout,
                                              max(1.0, remaining()))
            while time.monotonic() < deadline:
                if run_err:
                    raise RuntimeError(f"trainer died: {run_err[0]}")
                if cond():
                    return
                time.sleep(0.25)
            raise TimeoutError(f"timed out waiting for {what}")

        def rollup():
            return agg.rollup()

        # Warmup: age histogram flowing and the serving window
        # measurable (loadgen below fills the latter).
        wait_for(
            lambda: ((rollup().get("age_of_experience") or {})
                     .get("window") or {}).get("count", 0) > 0,
            180.0, "windowed age-of-experience on the rollup",
        )

        ld_holder: dict = {}

        def _loadgen():
            try:
                ld_holder["result"] = run_schedule_loadgen(
                    "127.0.0.1", fleet.port,
                    [(0.0, BASE_QPS), (T_SURGE, SURGE_QPS),
                     (T_IDLE, IDLE_QPS)],
                    clients=16, duration=DURATION,
                    obs_shape=pipe.comps.obs_shape, seed=11,
                    tick_s=1.0, conn_ttl_s=2.0, act_timeout=30.0,
                    stop_evt=ld_stop,
                )
            except BaseException as e:  # noqa: BLE001 — surfaced at verdict time
                ld_holder["error"] = f"{type(e).__name__}: {e}"

        ld_thread = threading.Thread(target=_loadgen, name="loadgen",
                                     daemon=True)
        ld_t0 = time.monotonic()
        ld_thread.start()

        def ld_elapsed() -> float:
            return time.monotonic() - ld_t0

        # -- 3. GREEN phase: every rule measurable, zero decisions ------
        wait_for(
            lambda: ((rollup().get("serving") or {})
                     .get("window") or {}).get("count", 0) > 0,
            120.0, "windowed serving latency on the rollup",
        )
        wait_for(lambda: ld_elapsed() >= T_SURGE - 3.0, T_SURGE + 30.0,
                 "end of the green baseline phase")
        green_rollup = rollup()
        green_decisions = pipe.autopilot.decisions
        # Governing-rule breaches only: the internal idle rule may
        # legitimately breach during boot (zero traffic at min size —
        # suppressed as at_min, never a decision).
        green_breaches = [e for e in events("slo_breach")
                          if e.get("rule") != "serving_idle"]

        # -- 4. serving surge: breach -> spawn -> windowed p99 re-held --
        wait_for(
            lambda: any(e.get("rule") == "serving_p99_ms"
                        for e in events("slo_breach")),
            90.0, "serving p99 slo_breach under surge",
        )
        wait_for(
            lambda: actions(fleet="serving", action="scale_up"),
            60.0, "autopilot serving scale_up",
        )
        wait_for(
            lambda: len(fleet.router.stats()["endpoints"]) >= 2
            and fleet.router.stats()["healthy"] >= 2,
            120.0, "replica 2 registered and healthy in the router",
        )
        wait_for(
            lambda: any(e.get("rule") == "serving_p99_ms"
                        for e in events("slo_clear")),
            120.0, "serving p99 slo_clear after scale-up",
        )
        surge_rollup = rollup()

        # -- 5. idle: scale-down on the zero-drop drain path ------------
        wait_for(
            lambda: actions(fleet="serving", action="scale_down"),
            T_IDLE + 120.0, "autopilot serving scale_down in the idle "
            "phase",
        )
        wait_for(
            lambda: events("replica_retired_done"),
            90.0, "retired replica reaped after drain + SIGTERM",
        )

        # -- 6. actor drill: kill-half -> quarantine -> grow -> re-held -
        # Two SIGKILLs against wid 1 (the second on the RESPAWNED
        # incarnation — pool.restarts gates the race) blow the
        # crash-loop budget: the supervisor quarantines it and the
        # fleet is down a slice until the autopilot grows wid 2.
        victim = 1
        restarts0 = pool.restarts
        os.kill(pool._procs[victim].pid, signal.SIGKILL)
        wait_for(lambda: pool.restarts > restarts0, 90.0,
                 "victim worker respawn ordered after first kill")
        os.kill(pool._procs[victim].pid, signal.SIGKILL)
        wait_for(lambda: victim in pool.quarantined, 90.0,
                 "victim worker quarantined (crash-loop budget)")
        wait_for(
            lambda: any(e.get("rule") == "age_p95_ms"
                        for e in events("slo_breach")),
            120.0, "age p95 slo_breach after the fleet shrank",
        )
        wait_for(
            lambda: actions(fleet="actor", action="scale_up"),
            60.0, "autopilot actor scale_up",
        )
        wait_for(
            lambda: 2 in pool.last_versions, 90.0,
            "grown wid 2 delivering experience",
        )
        wait_for(
            lambda: any(e.get("rule") == "age_p95_ms"
                        for e in events("slo_clear")),
            150.0, "age p95 slo_clear after the grow",
        )
        final_rollup = rollup()

        # Let the loadgen window close so zero-drops covers the run.
        wait_for(lambda: "result" in ld_holder or "error" in ld_holder,
                 DURATION + 60.0, "loadgen completion")
        ld_result = ld_holder.get("result") or {}
        if "error" in ld_holder:
            raise RuntimeError(f"loadgen died: {ld_holder['error']}")

        # -- 7. verdict + artifact --------------------------------------
        act_up_srv = actions(fleet="serving", action="scale_up")
        act_dn_srv = actions(fleet="serving", action="scale_down")
        act_up_act = actions(fleet="actor", action="scale_up")
        all_actions = events("autopilot_action")
        srv_breach = next(e for e in events("slo_breach")
                          if e.get("rule") == "serving_p99_ms")
        srv_clear = next(e for e in events("slo_clear")
                         if e.get("rule") == "serving_p99_ms")
        age_breach = next(e for e in events("slo_breach")
                          if e.get("rule") == "age_p95_ms")
        age_clear = next(e for e in events("slo_clear")
                         if e.get("rule") == "age_p95_ms")
        ap_state = pipe.autopilot.state()
        checks = {
            # The controller provably idles while every SLO is green.
            "no_action_while_green": green_decisions == 0
            and not green_breaches,
            "serving_breach_then_scale_up": bool(act_up_srv)
            and act_up_srv[0]["rule"] == "serving_p99_ms"
            and act_up_srv[0]["size_from"] == 1
            and act_up_srv[0]["size_to"] == 2
            and not act_up_srv[0]["dry_run"],
            "serving_one_step_at_a_time": len(act_up_srv) == 1,
            "serving_p99_reheld": srv_clear["seq"] > srv_breach["seq"]
            and srv_clear["value"] <= P99_BOUND_MS,
            "serving_scaled_down_on_idle": bool(act_dn_srv)
            and act_dn_srv[0]["rule"] == "serving_idle"
            and act_dn_srv[0]["size_to"] == 1,
            "serving_drain_zero_drops": bool(ld_result)
            and ld_result["timeouts"] + ld_result["errors"] == 0,
            "retired_replica_reaped": bool(
                events("replica_retired_done")),
            # The quarantined slice stays written off; the autopilot
            # restored baseline WIDTH from the reserved headroom.
            "actor_quarantine_shrank_fleet": victim in pool.quarantined
            and pool.live_workers() == [0, 2],
            "actor_breach_then_grow": bool(act_up_act)
            and act_up_act[0]["rule"] == "age_p95_ms"
            and act_up_act[0]["detail"] == {"wids": [2]},
            "grown_wid_on_reserved_partition": 2 in pool.last_versions,
            "age_p95_reheld": age_clear["seq"] > age_breach["seq"]
            and age_clear["value"] <= AGE_BOUND_MS,
            # Scale-down is drain+SIGTERM, never a kill: the fleet's
            # respawn counter would tick if a replica died any other way.
            "no_sigkill_on_scale_down": fleet.respawns == 0
            and fleet.retires == len(act_dn_srv),
            "zero_torn_records": pool.transport.summary()[
                "torn_records"] <= 1,   # the SIGKILL drill's salvage tear
            # Discovery plane: the replicas reached the sensor through
            # the membership registry (announce channel), and the
            # retired one LEFT it — no driver-side endpoint polling.
            "replicas_discovered_via_membership":
            "serving/replica0" in (final_rollup.get("endpoints") or {})
            and (final_rollup.get("membership") or {}).get("version", 0)
            > 0,
            "trainer_alive_throughout": not run_err,
        }
        verdict = {
            "ok": all(checks.values()),
            "checks": checks,
            "autopilot_actions": all_actions,
            "autopilot_state": ap_state,
            "slo_events": [
                {k: e.get(k) for k in ("event", "rule", "value",
                                       "bound", "burn")}
                for e in events()
                if e["event"] in ("slo_breach", "slo_clear")
            ],
            "green": {
                "decisions": green_decisions,
                "age_window": (green_rollup.get("age_of_experience")
                               or {}).get("window"),
                "serving_window": (green_rollup.get("serving")
                                   or {}).get("window"),
            },
            "surge_serving_window": (surge_rollup.get("serving")
                                     or {}).get("window"),
            "final": {
                "age_window": (final_rollup.get("age_of_experience")
                               or {}).get("window"),
                "live_workers": pool.live_workers(),
                "quarantined": sorted(pool.quarantined),
                "grows": pool.grows,
                "retires": pool.retires,
                "serving_active": fleet.active_replicas(),
                "serving_spawned": fleet.spawned,
                "serving_retires": fleet.retires,
            },
            "loadgen": {
                k: ld_result.get(k)
                for k in ("schedule", "phases", "requests", "shed",
                          "timeouts", "errors", "reconnects", "checks")
            },
            "rendered": render_fleet(
                {"fleet": final_rollup, "slo": agg.slo_status(),
                 "autopilot": ap_state}
            ).splitlines(),
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
    except (TimeoutError, RuntimeError) as e:
        verdict = {
            "ok": False, "error": f"{type(e).__name__}: {e}",
            "autopilot_state": (pipe.autopilot.state()
                                if pipe is not None
                                and pipe.autopilot is not None else None),
            "events_tail": _tail_jsonl(trainer_log)[-40:],
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
    finally:
        ld_stop.set()
        if pipe is not None:
            pipe.stop_event.set()
        if run_thread is not None:
            run_thread.join(timeout=60.0)
        if fleet is not None:
            fleet.stop()

    line = json.dumps(verdict)
    if args.out == "-":
        print(line)
    else:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=1)
        print(line[:600])
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
