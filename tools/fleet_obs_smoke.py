#!/usr/bin/env python
"""Fleet-observability smoke gate (tools/verify_t1.sh gate 11).

The fleet-wide observability plane end to end, CI-sized, on real
processes:

  1. a 2-shard ReplayServiceFleet comes up (auto-respawn on), and a
     trainer attaches over the replay RPC plane with process actors and
     full tracing (``obs.trace_sample_rate=1.0``) + an ephemeral obs
     exporter;
  2. a 2-replica ServingFleet comes up behind the router (real serve.py
     children on the delta param hub) and takes a small client burst so
     replicas have latency histograms to merge;
  3. a FleetAggregator discovers all five endpoints (trainer /varz,
     2 shards via the endpoints file + stats RPC, 2 replicas via their
     announced obs ports), scrapes on a cadence, and serves the rollup;
     the smoke asserts the rollup merges histograms from BOTH shards and
     BOTH replicas with per-endpoint liveness, and that at least one
     end-to-end trace timeline spans >= 3 distinct pids across an RPC
     hop (worker act span -> trainer add-RPC client span -> shard server
     span);
  4. one shard is SIGKILLed mid-run: the SLO engine's endpoint-liveness
     rule must fire a damped ``slo_breach`` (burn-rate window, not one
     bad scrape), the fleet must respawn the shard, the aggregator must
     re-resolve it through the republished endpoints file, and
     ``slo_clear`` must follow — the exact breach/clear pair the elastic
     autopilot will actuate on;
  5. NEW — the flight-data recorder leg: the aggregator carries a
     TimelineStore from its first sweep, so before the drill the smoke
     asserts the windowed serving p99 recomputed FROM DISK is
     bit-identical to the live in-memory rollup window; then, while the
     liveness rule is still IN BREACH from the shard kill, the
     aggregator itself is crashed (dropped without close — uncommitted
     timeline tail, exactly a SIGKILL) and a fresh aggregator + cold
     SloEngine adopt the tail and rebuild the burn windows: the rebuilt
     rule must come back already in ``breach`` with its window samples
     restored (no blind window), emit NO duplicate breach, and the
     eventual ``slo_clear`` must be the genuine post-respawn one — zero
     false clears.  A trace exemplar pulled from the timeline's replay
     p99 latency bucket must link to an assembled >=3-pid trace
     timeline, and ``tools/obs_diff.py`` self-checks the run against
     the previously committed ``demos/timeline.json``;
  6. the committed artifacts (``demos/fleet_obs.json``,
     ``demos/timeline.json`` via ``--timeline-out``) carry the rollup
     snapshot, the multi-pid timeline, the breach/clear events, the
     timeline summary + SLO-rebuild proof, and rendered
     ``obs_top --fleet`` / ``obs_top --timeline`` frames.

    python tools/fleet_obs_smoke.py [--out demos/fleet_obs.json]
        [--timeline-out demos/timeline.json]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OBS = (6,)
CAPACITY = 4096


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
    ap = argparse.ArgumentParser(prog="fleet_obs_smoke")
    ap.add_argument("--out", default="-")
    ap.add_argument("--timeline-out", default=None, metavar="FILE",
                    help="also write the timeline demo artifact "
                    "(summary + proofs) here")
    ap.add_argument("--deadline", type=float, default=420.0)
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"  # before the first jax import
    import numpy as np

    from ape_x_dqn_tpu.config import ApexConfig, apply_overrides
    from ape_x_dqn_tpu.obs.fleet import FleetAggregator, SloEngine, SloRule
    from ape_x_dqn_tpu.obs.fleet import _endpoints_down
    from ape_x_dqn_tpu.obs.timeline import TimelineStore, read_timeline
    from ape_x_dqn_tpu.replay.service import ReplayServiceFleet
    from ape_x_dqn_tpu.runtime.components import build_components
    from ape_x_dqn_tpu.serving import ServingClient, ServingFleet
    from tools import obs_diff
    from tools.obs_top import render_fleet, render_timeline

    t_start = time.monotonic()

    def remaining() -> float:
        return args.deadline - (time.monotonic() - t_start)

    tmp = tempfile.mkdtemp(prefix="fleet-obs-smoke-")
    trainer_log = os.path.join(tmp, "trainer.jsonl")
    verdict = {"ok": False}
    slo_events: list = []
    trainer = None
    replay_fleet = None
    serving_fleet = None
    agg = None
    try:
        # -- 1. serving fleet first (replicas pay a jax import each; boot
        # them before the trainer is burning the same cores).  The first
        # publish lands BEFORE start: a replica blocks on its initial
        # param sync before announcing ports (the hub serves the stored
        # snapshot to fresh connections).
        cfg = apply_overrides(ApexConfig(), [
            "network=mlp", "env.name=chain:6", "serving.max_wait_ms=2.0",
        ])
        comps = build_components(cfg)
        serving_fleet = ServingFleet(
            replicas=2, probe_interval_s=0.5,
            replica_args=["--set", "network=mlp",
                          "--set", "env.name=chain:6"],
        )
        serving_fleet.publish(comps.state.params)
        serving_fleet.start(timeout=min(240.0, remaining()))
        # Burst over MANY connections: the router balances at connection
        # granularity, so per-connection clients spread the load and BOTH
        # replicas end up with latency buckets for the rollup to merge.
        obs0 = np.zeros(comps.obs_shape, np.uint8)
        served = 0
        for c in range(8):
            client = ServingClient("127.0.0.1", serving_fleet.port, seed=c)
            for _ in range(5):
                try:
                    client.act(obs0, timeout=10.0)
                    served += 1
                except Exception:  # noqa: BLE001 — a shed under warmup is fine; the count gates below
                    pass
            client.close()

        # -- 2. replay fleet + attached trainer.  The respawn backoff is
        # deliberately SLOW for a shard (seconds, not the sub-second
        # numpy spawn): the SLO drill below needs a real outage window —
        # a shard that resurrects inside one scrape tick never
        # accumulates burn, which is the damping WORKING, not a breach.
        replay_fleet = ReplayServiceFleet(
            2, CAPACITY, OBS, root_dir=os.path.join(tmp, "replay"),
            save_every_s=1.0, respawn_base_s=5.0, respawn_max_s=8.0,
        ).start(timeout=min(60.0, remaining()))
        env = {**os.environ, "PYTHONPATH": REPO}
        trainer = subprocess.Popen(
            [sys.executable, "-m", "ape_x_dqn_tpu", "--steps", "200000",
             "--log-every", "50", "--metrics-file", trainer_log,
             "--set", "network=mlp", "--set", "env.name=chain:6",
             "--set", f"replay.capacity={CAPACITY}",
             "--set", "replay.service_mode=attach",
             "--set",
             f"replay.service_endpoints={replay_fleet.endpoints_path}",
             "--set", "replay.service_probe_interval_s=0.25",
             "--set", "replay.service_request_timeout_s=3.0",
             "--set", "learner.min_replay_mem_size=400",
             "--set", "learner.total_steps=200000",
             "--set", "actor.T=100000000",
             "--set", "actor.mode=process", "--set", "actor.num_workers=1",
             "--set", "actor.num_actors=2",
             "--set", "obs.export_port=0",
             "--set", "obs.trace_sample_rate=1.0"],
            cwd=REPO, env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, "trainer.err"), "wb"),
        )

        def wait_for(cond, timeout, what):
            deadline = time.monotonic() + min(timeout, max(1.0, remaining()))
            while time.monotonic() < deadline:
                if cond():
                    return
                if trainer.poll() is not None:
                    raise RuntimeError(
                        f"trainer exited rc={trainer.returncode} while "
                        f"waiting for {what}"
                    )
                time.sleep(0.25)
            raise TimeoutError(f"timed out waiting for {what}")

        def trainer_obs_port():
            for r in _tail_jsonl(trainer_log):
                if r.get("event") == "obs_exporter":
                    return int(r["port"])
            return None

        wait_for(lambda: trainer_obs_port() is not None, 240.0,
                 "trainer obs exporter announce")

        # -- 3. the aggregator over all five endpoints, with the
        # flight-data recorder attached from the very first sweep -------
        tl_dir = os.path.join(tmp, "timeline")

        def mk_slo(sink):
            return SloEngine(
                [SloRule("endpoints_alive", "upper", 0.0,
                         _endpoints_down)],
                window_s=8.0, burn_threshold=0.4, clear_threshold=0.15,
                min_samples=3,
                emit=lambda name, **f: sink.append(
                    {"event": name,
                     "t": round(time.monotonic() - t_start, 2), **f}
                ),
            )

        t_port = trainer_obs_port()

        def wire(a):
            a.add_varz("trainer0", f"http://127.0.0.1:{t_port}/varz",
                       kind="trainer")
            for rid, rep in serving_fleet.replicas.items():
                a.add_varz(f"replica{rid}",
                           f"http://127.0.0.1:{rep.obs_port}/varz",
                           kind="replica")
            a.watch_replay_endpoints(replay_fleet.endpoints_path)

        agg = FleetAggregator(
            scrape_interval_s=0.3, scrape_timeout_s=1.5,
            window_s=60.0, slo=mk_slo(slo_events),
        )
        agg.attach_timeline(TimelineStore(tl_dir))
        wire(agg)
        agg.serve(port=0)
        agg.start()

        def rollup():
            return agg.rollup()

        wait_for(
            lambda: rollup().get("alive", 0) == 5, 120.0,
            "all five endpoints scraped alive",
        )
        wait_for(
            lambda: (rollup().get("age_of_experience") or {})
            .get("count", 0) > 0, 180.0,
            "merged age-of-experience histogram",
        )
        wait_for(
            lambda: any(
                len(t.get("pids", [])) >= 3
                for t in rollup().get("traces", [])
            ), 180.0,
            "a >=3-pid cross-tier trace timeline",
        )
        healthy = rollup()
        multi_pid_trace = next(
            t for t in healthy["traces"] if len(t["pids"]) >= 3
        )

        # -- 3b. windowed p99 FROM DISK vs the live in-memory rollup.
        # Same delta sequence, same merge + bucket_percentile arithmetic,
        # same inclusive window bounds -> the numbers must be IDENTICAL,
        # not merely close.  Retried because the sweep thread is live: a
        # sweep landing between the two reads skews one side for a tick.
        store = agg.timeline
        wait_for(
            lambda: ((rollup().get("serving") or {}).get("window") or {})
            .get("count", 0) > 0, 60.0,
            "serving deltas in the trailing window",
        )
        live_p99 = disk_p99 = None
        p99_match = False
        for _ in range(40):
            st0 = store.stats()
            win = (rollup().get("serving") or {}).get("window") or {}
            live_p99 = win.get("p99_ms")
            st1 = store.stats()
            if live_p99 is not None and st1["t_last"] is not None \
                    and st0["t_last"] == st1["t_last"]:
                d = store.percentile("serving_s", 99,
                                     st1["t_last"] - 60.0,
                                     st1["t_last"])
                disk_p99 = round(d * 1e3, 3) if d is not None else None
                if disk_p99 == live_p99:
                    p99_match = True
                    break
            time.sleep(0.15)

        # -- 4. SIGKILL one shard: breach fires on the live engine ---------
        kill_rec = replay_fleet.kill_random()
        victim = kill_rec["shard"]
        wait_for(
            lambda: any(e["event"] == "slo_breach" for e in slo_events),
            60.0, "slo_breach after the shard kill",
        )
        time.sleep(0.7)   # let the breach-state sweep commit to disk

        # -- 4b. crash the aggregator WHILE IN BREACH.  The store is
        # detached before close so the active segment is never committed
        # — an uncommitted tail on disk, exactly what SIGKILL leaves.
        # The SloEngine dies with its burn window; a cold replacement
        # would restart blind ("ok", zero samples) and re-derive state
        # from scratch — the flap the timeline rebuild exists to kill.
        agg.timeline = None
        agg.close()
        agg = None
        slo_events2: list = []
        store2 = TimelineStore(tl_dir)        # adopts the torn tail
        adopted = store2.stats()["adopted_records"]
        agg2 = FleetAggregator(
            scrape_interval_s=0.3, scrape_timeout_s=1.5,
            window_s=60.0, slo=mk_slo(slo_events2),
        )
        agg2.attach_timeline(store2)          # rebuilds the burn windows
        rebuilt = agg2.slo_status()["rules"]["endpoints_alive"]
        wire(agg2)
        agg2.start()
        agg = agg2       # the finally block now owns the replacement

        # -- 4c. the REAL clear: shard respawns, the rebuilt engine (which
        # came back already in breach, burn window intact) emits the one
        # genuine slo_clear — no duplicate breach, no blind-window flap.
        wait_for(
            lambda: replay_fleet.shards[victim].alive(), 60.0,
            "shard respawn",
        )
        wait_for(
            lambda: any(e["event"] == "slo_clear" for e in slo_events2),
            90.0, "slo_clear from the REBUILT engine after recovery",
        )
        wait_for(
            lambda: rollup().get("alive", 0) == 5, 60.0,
            "all five endpoints alive on the restarted aggregator",
        )
        final = rollup()

        # -- 5. verdict + artifacts ---------------------------------------
        final_slo = agg2.slo_status()
        agg2.close()     # clean close COMMITS the active segment
        agg = None

        # Exemplar -> assembled trace: a trace id sampled into the replay
        # op latency buckets must join up with a >=3-pid timeline the
        # aggregator assembled from TraceSpanLog spans.
        tl_doc = read_timeline(tl_dir)
        multi_ids = {
            t["trace_id"]
            for src in (healthy, final)
            for t in (src.get("traces") or [])
            if len(t.get("pids", [])) >= 3
        }
        p99_op_s = store2.percentile("replay_op_s", 99) or 0.0
        exemplar_hits = []
        for rec in tl_doc["records"]:
            for edge, tid in ((rec.get("exemplars") or {})
                              .get("replay_op") or {}).items():
                if tid in multi_ids:
                    exemplar_hits.append(
                        {"t": rec["t"], "bucket_le_s": edge,
                         "trace_id": tid,
                         "tail_bucket": float(edge) >= p99_op_s}
                    )
        linked = next((h for h in exemplar_hits if h["tail_bucket"]),
                      exemplar_hits[-1] if exemplar_hits else None)
        linked_trace = next(
            (t for src in (final, healthy)
             for t in (src.get("traces") or [])
             if linked and t["trace_id"] == linked["trace_id"]), None,
        )

        # obs_diff self-check: this run vs the previously committed demo.
        tl_summary = obs_diff.summarize(tl_doc)
        prev_demo = os.path.join(REPO, "demos", "timeline.json")
        diff_report = None
        if os.path.exists(prev_demo):
            try:
                diff_report = obs_diff.diff(
                    obs_diff.load_side(prev_demo), tl_summary
                )
            except (ValueError, OSError) as e:
                diff_report = {"error": f"{type(e).__name__}: {e}"}

        shard_eps = {n: e for n, e in healthy["endpoints"].items()
                     if e["kind"] == "shard"}
        replica_eps = {n: e for n, e in healthy["endpoints"].items()
                       if e["kind"] == "replica"}
        breach = next(e for e in slo_events if e["event"] == "slo_breach")
        clear = next(e for e in slo_events2
                     if e["event"] == "slo_clear")
        checks = {
            "five_endpoints_alive": healthy["alive"] == 5,
            "two_shards_in_rollup": len(shard_eps) == 2
            and all(e["alive"] for e in shard_eps.values()),
            "two_replicas_in_rollup": len(replica_eps) == 2
            and all(e["alive"] for e in replica_eps.values()),
            # Merged histograms: shard op_ms buckets from BOTH shards
            # (requests spread over both), replica latency buckets from
            # the burst through the router.
            "shard_histograms_merged": bool(
                healthy["replay"]["op_buckets"]
                and healthy["replay"]["shards_alive"] == 2
                # BOTH shards served requests into the merged histogram.
                and all((e["detail"] or {}).get("requests", 0) > 0
                        for e in shard_eps.values())
            ),
            "replica_histograms_merged": (
                healthy["serving"]["count"] >= served > 0
                and bool(healthy["serving"]["latency_buckets"])
                # BOTH replicas contributed requests to the merge.
                and all((e["detail"] or {}).get("requests", 0) > 0
                        for e in replica_eps.values())
            ),
            "age_histogram_merged": healthy["age_of_experience"]["count"] > 0,
            "trace_spans_three_pids": len(multi_pid_trace["pids"]) >= 3,
            "trace_crosses_rpc_hop": any(
                h.startswith("rsvc.") for h in multi_pid_trace["hops"]
            ),
            "slo_breach_fired": breach["rule"] == "endpoints_alive",
            "shard_respawned": replay_fleet.respawns >= 1,
            "slo_clear_followed": clear["t"] > breach["t"],
            "rollup_alive_through_outage": agg2.sweeps > 0
            and final["alive"] >= 4,
            # -- flight-data recorder proofs --------------------------------
            "timeline_p99_disk_matches_live": p99_match,
            "timeline_tail_adopted_after_sigkill": adopted > 0,
            # The rebuilt engine came back ALREADY in breach with its burn
            # window restored — before its first scrape.  A cold engine
            # would read "ok"/0 samples here: the blind window.
            "slo_burn_window_rebuilt_in_breach": (
                rebuilt["state"] == "breach" and rebuilt["samples"] >= 3
            ),
            # The only post-restart transition is the one genuine clear:
            # no duplicate breach (state carried over), no false clear
            # (the clear waited for the actual respawn).
            "no_false_transitions_after_restart": (
                [e["event"] for e in slo_events2] == ["slo_clear"]
            ),
            "timeline_exemplar_links_multi_pid_trace": (
                linked is not None and linked_trace is not None
                and len(linked_trace["pids"]) >= 3
            ),
            "obs_diff_report": diff_report is None or (
                "error" not in diff_report
                and bool(diff_report.get("rows"))
            ),
        }
        timeline_proofs = {
            "p99_disk_vs_live": {"live_ms": live_p99, "disk_ms": disk_p99,
                                 "match": p99_match},
            "slo_rebuild": {
                "adopted_records": adopted,
                "rebuilt_rule": rebuilt,
                "events_after_restart": slo_events2,
            },
            "exemplar_link": {
                "p99_op_s": round(p99_op_s, 6),
                "hit": linked,
                "trace_pids": (linked_trace or {}).get("pids"),
                "trace_hops": (linked_trace or {}).get("hops"),
            },
            "obs_diff": diff_report,
        }
        verdict = {
            "ok": all(checks.values()),
            "checks": checks,
            "kill": kill_rec,
            "slo_events": slo_events,
            "rollup": {
                k: healthy[k] for k in (
                    "endpoints", "alive", "expected", "scrapes",
                    "scrape_failures", "age_of_experience", "serving",
                    "replay", "inference", "ring_occupancy_max",
                )
            },
            "trace_timeline": multi_pid_trace,
            "rollup_after_recovery": {
                k: final[k] for k in ("alive", "expected",
                                      "scrape_failures")
            },
            "slo_status": final_slo,
            "timeline": timeline_proofs,
            "timeline_varz": store2.stats(),
            "rendered": render_fleet(
                {"fleet": healthy, "slo": final_slo}
            ).splitlines(),
            "served_burst": served,
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
        if args.timeline_out:
            with open(args.timeline_out, "w") as f:
                json.dump({
                    "ok": verdict["ok"],
                    "proofs": timeline_proofs,
                    "checks": {k: v for k, v in checks.items()
                               if k.startswith(("timeline", "slo_burn",
                                                "no_false", "obs_diff"))},
                    "timeline_summary": tl_summary,
                    "timeline_varz": store2.stats(),
                    "rendered": render_timeline(tl_doc).splitlines(),
                }, f, indent=1)
    except (TimeoutError, RuntimeError) as e:
        verdict = {"ok": False, "error": f"{type(e).__name__}: {e}",
                   "slo_events": slo_events,
                   "rollup": agg.rollup() if agg is not None else None,
                   "elapsed_s": round(time.monotonic() - t_start, 1)}
        try:
            with open(os.path.join(tmp, "trainer.err")) as f:
                tail = f.read()[-1500:]
            if tail.strip():
                verdict["trainer_stderr"] = tail
        except OSError:
            pass
    finally:
        if agg is not None:
            agg.close()
        if trainer is not None and trainer.poll() is None:
            trainer.terminate()
            try:
                trainer.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                trainer.kill()
        if serving_fleet is not None:
            serving_fleet.stop()
        if replay_fleet is not None:
            replay_fleet.stop()

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
