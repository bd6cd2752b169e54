#!/usr/bin/env python
"""Elastic-replay smoke gate (tools/verify_t1.sh gate 13).

The replay service as the third autopilot-governed fleet, CI-sized, on
real shard processes and the real discovery plane — no jax, no trainer:

  1. a standalone membership registry (fleet/registry.py) is the ONE
     source of routing truth: a 2-shard ReplayServiceFleet announces
     every shard over F_FANN, the learner-facing ShardedReplayClient is
     built with ``from_registry`` (it never reads an endpoints file),
     and the FleetAggregator adopts its scrape set from
     ``bind_registry`` — no driver hands a port to anything;
  2. FLOOR phase: with zero ingest the idle rule breaches immediately,
     and the controller provably decides NOTHING — every scale-down
     impulse is suppressed ``at_min`` at the 2-shard floor;
  3. ingest surge: ~25 chunks/s of 16 transitions push per-shard add
     QPS far over ``obs.fleet_slo_replay_add_qps_high`` → burn-windowed
     ``slo_breach`` → the autopilot calls ``ReplayServiceFleet.grow()``
     (2 → 3); the new shard ANNOUNCES itself and both the client and
     the aggregator adopt it from membership alone, after which
     round-robin adds land real data on the new slot range;
  4. ingest stops: the breach clears, the controller's own
     ``replay_idle`` burn window trips, and the autopilot retires the
     highest shard — drain → live crc fingerprint → SIGTERM (final
     committed chain) → restore → PROVE bit-exact → re-add every held
     transition into the survivors (``reshard_done`` must carry
     ``digest_ok`` and ``lost == 0``);
  5. the client keeps sampling across both reshards, and the committed
     artifact (``demos/elastic_replay.json``) carries the action trail,
     the reshard/SLO event streams, and an ``obs_top --fleet`` frame
     with the membership row.

    python tools/elastic_replay_smoke.py [--out demos/elastic_replay.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OBS = (6,)
SHARD_CAP = 2048
CHUNK = 16
HOT_CHUNK_HZ = 25.0          # ~400 adds/s fleet-wide while hot
ADD_QPS_BOUND = 40.0         # per-shard grow bound (hot runs ~5x over)
IDLE_BOUND = 4.0             # per-shard idle (retire) bound
SOAK_AFTER_GROW_S = 3.0      # keep ingest up so sid 2 holds real data


class _Batch:
    def __init__(self, arrays):
        for k, v in arrays.items():
            setattr(self, k, v)


def _chunk(rng, n=CHUNK):
    obs = rng.integers(0, 255, (n, *OBS), dtype="uint8")
    return {
        "prio": (abs(rng.normal(size=n)) + 0.1).astype("float64"),
        "obs": obs,
        "action": rng.integers(0, 2, n).astype("int32"),
        "reward": rng.normal(size=n).astype("float32"),
        "discount": [0.99] * n,
        "next_obs": rng.integers(0, 255, (n, *OBS), dtype="uint8"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="elastic_replay_smoke")
    ap.add_argument("--out", default="-")
    ap.add_argument("--deadline", type=float, default=300.0)
    args = ap.parse_args(argv)

    import numpy as np

    from ape_x_dqn_tpu.autopilot import (
        AutopilotController,
        ReplayFleetActuator,
    )
    from ape_x_dqn_tpu.config import ApexConfig, apply_overrides
    from ape_x_dqn_tpu.fleet.registry import FleetRegistry
    from ape_x_dqn_tpu.obs.fleet import FleetAggregator, engine_from_config
    from ape_x_dqn_tpu.replay.service import (
        ReplayServiceFleet,
        ShardedReplayClient,
    )
    from tools.obs_top import render_fleet

    t_start = time.monotonic()

    def remaining() -> float:
        return args.deadline - (time.monotonic() - t_start)

    # Every tier reports into ONE in-memory event stream: the verdict's
    # phase assertions read the same records a JSONL sink would carry.
    ev_lock = threading.Lock()
    ev_log: list = []

    # First param deliberately not ``kind``: slo/reshard events carry a
    # ``kind=...`` field of their own.
    def emit(name, **fields):
        with ev_lock:
            ev_log.append(dict(fields, event=name))

    def events(kind=None):
        with ev_lock:
            recs = list(ev_log)
        if kind is None:
            return recs
        return [r for r in recs if r["event"] == kind]

    def actions(**match):
        return [r for r in events("autopilot_action")
                if all(r.get(k) == v for k, v in match.items())]

    def wait_for(cond, timeout, what):
        deadline = time.monotonic() + min(timeout, max(1.0, remaining()))
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.1)
        raise TimeoutError(f"timed out waiting for {what}")

    cfg = apply_overrides(ApexConfig(), [
        # Breach-side SLO: per-shard add RATE (the signal that stays
        # comparable across reshards), burn-windowed tight for CI.
        f"obs.fleet_slo_replay_add_qps_high={ADD_QPS_BOUND}",
        "obs.fleet_slo_endpoint_alive=false",
        "obs.fleet_slo_window_s=4",
        "obs.fleet_slo_burn_threshold=0.5",
        "obs.fleet_slo_clear_threshold=0.25",
        "obs.fleet_slo_min_samples=3",
        # The controller under test: replay bounds 2..3, fast cadences.
        "autopilot.enabled=true", "autopilot.poll_s=0.5",
        "autopilot.replay_min_shards=2",
        "autopilot.replay_max_shards=3",
        f"autopilot.replay_idle_add_qps_per_shard={IDLE_BOUND}",
        "autopilot.idle_window_s=6",
        "autopilot.cooldown_up_s=2",
        "autopilot.cooldown_down_s=2",
        "autopilot.hold_opposite_s=1.5",
        "fleet.discovery=registry",
    ])

    tmp = tempfile.mkdtemp(prefix="elastic-replay-smoke-")
    verdict = {"ok": False}
    reg = fleet = cl = agg = ctl = None
    ingest_stop = threading.Event()
    ingest_thread = None
    ingest_err: list = []
    adds = {"n": 0}
    try:
        # -- 1. discovery plane + the three tiers ----------------------
        reg = FleetRegistry(token=0x5EED, ttl_s=5.0,
                            on_event=emit).serve()
        fleet = ReplayServiceFleet(
            2, 2 * SHARD_CAP, OBS, root_dir=os.path.join(tmp, "replay"),
            token=reg.token, registry_addr=("127.0.0.1", reg.port),
            heartbeat_s=0.25, save_every_s=0.5, on_event=emit,
        )
        fleet.start(timeout=min(60.0, remaining()))
        cl = ShardedReplayClient.from_registry(
            "127.0.0.1", reg.port, token=reg.token,
            wait_timeout_s=min(30.0, remaining()),
            probe_interval_s=0.25, on_event=emit,
        )
        engine = engine_from_config(cfg.obs, emit)
        agg = FleetAggregator(scrape_interval_s=0.5, slo=engine,
                              window_s=cfg.obs.fleet_slo_window_s,
                              emit=emit)
        agg.bind_registry(reg)
        ctl = AutopilotController(cfg.autopilot, rollup_fn=agg.rollup,
                                  emit=emit)
        ctl.attach_replay(ReplayFleetActuator(fleet, drain_grace_s=0.5))
        engine.subscribe(ctl.on_slo_event)
        agg.start()
        ctl.start()

        wait_for(
            lambda: (agg.rollup().get("replay") or {})
            .get("shards_alive") == 2,
            30.0, "both seed shards scraped via membership",
        )

        # -- 2. FLOOR phase: idle impulse suppressed at_min ------------
        wait_for(
            lambda: ctl.suppressed.get("replay:down:at_min", 0) > 0,
            45.0, "idle scale-down suppressed at the 2-shard floor",
        )
        floor_decisions = ctl.decisions

        # -- 3. ingest surge: breach -> grow -> membership adoption ----
        rng = np.random.default_rng(17)

        def _ingest():
            try:
                while not ingest_stop.wait(1.0 / HOT_CHUNK_HZ):
                    arrays = _chunk(rng)
                    cl.add(np.asarray(arrays["prio"]), _Batch(arrays))
                    adds["n"] += CHUNK
            except BaseException as e:  # noqa: BLE001 — surfaced at verdict time
                ingest_err.append(f"{type(e).__name__}: {e}")

        ingest_thread = threading.Thread(target=_ingest, name="ingest",
                                         daemon=True)
        ingest_thread.start()
        wait_for(
            lambda: any(e.get("rule") == "replay_add_qps"
                        for e in events("slo_breach")),
            60.0, "replay_add_qps slo_breach under ingest",
        )
        wait_for(
            lambda: actions(fleet="replay", action="scale_up"),
            30.0, "autopilot replay scale_up",
        )
        wait_for(
            lambda: cl.num_shards == 3
            and cl.stats()["membership_version"] > 0,
            30.0, "client adopted the grown shard from membership",
        )
        wait_for(
            lambda: (agg.rollup().get("replay") or {})
            .get("shards_alive") == 3,
            30.0, "aggregator adopted + scraped the grown shard",
        )
        # Round-robin lands real transitions on the new slot range —
        # the retire below must hand data back, not an empty ring.
        wait_for(
            lambda: cl._sizes.get(2, 0) >= CHUNK,
            SOAK_AFTER_GROW_S + 20.0, "grown shard holding transitions",
        )
        time.sleep(SOAK_AFTER_GROW_S)
        hot_rollup = agg.rollup()
        hot_sample = cl.sample(32, rng=np.random.default_rng(1))
        assert hot_sample.indices.shape == (32,)

        # -- 4. cold: clear -> replay_idle -> digest-proven retire -----
        ingest_stop.set()
        ingest_thread.join(timeout=10.0)
        wait_for(
            lambda: any(e.get("rule") == "replay_add_qps"
                        for e in events("slo_clear")),
            60.0, "replay_add_qps slo_clear after ingest stopped",
        )
        wait_for(
            lambda: actions(fleet="replay", action="scale_down"),
            90.0, "autopilot replay scale_down on replay_idle",
        )
        wait_for(
            lambda: any(e.get("kind") == "retire"
                        for e in events("reshard_done")),
            90.0, "digest-proven retire handoff",
        )
        wait_for(
            lambda: cl.num_shards == 2
            and (agg.rollup().get("replay") or {})
            .get("shards_alive") == 2,
            30.0, "client + aggregator back to 2 shards via membership",
        )

        # -- 5. verdict + artifact -------------------------------------
        cold_sample = cl.sample(32, rng=np.random.default_rng(2))
        act_up = actions(fleet="replay", action="scale_up")
        act_dn = actions(fleet="replay", action="scale_down")
        grow_done = next(e for e in events("reshard_done")
                         if e.get("kind") == "grow")
        retire_done = next(e for e in events("reshard_done")
                           if e.get("kind") == "retire")
        routing = [e.get("shards") for e
                   in events("replay_routing_changed")]
        final_rollup = agg.rollup()
        mem = final_rollup.get("membership") or {}
        cl_stats = cl.stats()
        if ingest_err:
            raise RuntimeError(f"ingest died: {ingest_err[0]}")
        checks = {
            # Membership, not the endpoints file, drives routing: the
            # client was built WITHOUT a path and adopted every reshard.
            "membership_drives_routing": cl._endpoints_path is None
            and cl_stats["membership_version"] > 0
            and cl_stats["membership_adopts"] >= 2,
            "no_action_at_floor": floor_decisions == 0
            and ctl.suppressed.get("replay:down:at_min", 0) > 0,
            "ingest_breach_then_grow": bool(act_up)
            and act_up[0]["rule"] == "replay_add_qps"
            and act_up[0]["size_from"] == 2
            and act_up[0]["size_to"] == 3
            and act_up[0]["detail"] == {"sid": 2}
            and not act_up[0]["dry_run"],
            "one_step_at_a_time": len(act_up) == 1,
            "grown_shard_adopted_everywhere":
            "replay_shard2" in (hot_rollup.get("endpoints") or {})
            and [0, 1, 2] in routing,
            "idle_clear_then_scale_down": bool(act_dn)
            and act_dn[0]["rule"] == "replay_idle"
            and act_dn[0]["size_from"] == 3
            and act_dn[0]["size_to"] == 2
            and act_dn[0]["detail"] == {"sid": 2},
            "retire_digest_proven": retire_done["digest_ok"]
            and retire_done["count"] > 0
            and "crc" in retire_done,
            "zero_lost_transitions": retire_done["lost"] == 0
            and retire_done["transferred"] > 0,
            "routing_followed_both_reshards": [0, 1, 2] in routing
            and routing and routing[-1] == [0, 1],
            "client_sampled_through_reshards":
            cold_sample.indices.shape == (32,)
            and cl.size() > 0 and not cl.degraded,
            "grow_was_empty_split": grow_done["transferred"] == 0
            and grow_done["lost"] == 0,
        }
        verdict = {
            "ok": all(checks.values()),
            "checks": checks,
            "adds_total": adds["n"],
            "autopilot_actions": events("autopilot_action"),
            "autopilot_state": ctl.state(),
            "reshard_events": [
                e for e in events()
                if e["event"].startswith("reshard_")
            ],
            "slo_events": [
                {k: e.get(k) for k in ("event", "rule", "value",
                                       "bound", "burn")}
                for e in events()
                if e["event"] in ("slo_breach", "slo_clear")
            ],
            "routing_versions": routing,
            "membership": mem,
            "registry": reg.stats(),
            "replay_client": {
                k: cl_stats.get(k)
                for k in ("shards", "size", "total_mass", "adds",
                          "membership_version", "membership_adopts",
                          "updates_dropped", "shards_down")
            },
            "hot_replay": hot_rollup.get("replay"),
            "final_replay": final_rollup.get("replay"),
            "rendered": render_fleet(
                {"fleet": final_rollup, "slo": agg.slo_status(),
                 "autopilot": ctl.state()}
            ).splitlines(),
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
    except (TimeoutError, RuntimeError, AssertionError) as e:
        verdict = {
            "ok": False, "error": f"{type(e).__name__}: {e}",
            "autopilot_state": ctl.state() if ctl is not None else None,
            "events_tail": events()[-40:],
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
    finally:
        ingest_stop.set()
        if ingest_thread is not None:
            ingest_thread.join(timeout=10.0)
        if ctl is not None:
            ctl.close()
        if agg is not None:
            agg.close()
        if cl is not None:
            cl.close()
        if fleet is not None:
            fleet.stop()
        if reg is not None:
            reg.close()

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
