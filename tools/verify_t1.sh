#!/usr/bin/env bash
# Tier-1 verify — the ONE blessed entry point for builders and CI.
# Gate 1: compileall — an import-time syntax regression anywhere in the
#         package or tools fails in seconds, not after the whole pytest run.
# Gate 2: xp_transport smoke — tools/xp_transport.py --smoke: one
#         CI-sized transport point + SIGKILL barrage (host-only, no jax),
#         so a regression in the experience transport can't reach the
#         driver unseen.
# Gate 3: checkpoint round-trip smoke — train on the tiny config with
#         incremental checkpointing, SIGKILL mid-run, resume from the
#         committed manifest and train past it (tools/ckpt_smoke.py).
# Gate 4: observability smoke — the process-actor pipeline with the
#         exporter on an ephemeral port: scrape /metrics + /varz +
#         /healthz, SIGKILL a worker, assert the salvaged shm stats
#         block lands as a post-mortem file and lineage spans complete
#         (tools/obs_smoke.py).
# Gate 5: chaos smoke — the fault-tolerance contract, CI-sized: a
#         2-worker supervised run takes one SIGKILL (supervised respawn),
#         one SIGKILL + injected torn ring record (salvage counts it,
#         never ingests it), then a committed APXC chunk is bit-flipped
#         and the resume must walk the chain back (fallback restore) and
#         train past the restored step — tools/chaos_smoke.py.
# Gate 6: tiered-replay spill smoke — a hot-budgeted replay (most spans
#         cold on disk) must sample bit-exactly against its dense twin
#         with evictions forced between every op, then survive a SIGKILL
#         mid-spill: the committed chain restores bit-exactly (cold
#         spans adopted in place, CRC-verified) and trains past the
#         restored step — tools/spill_smoke.py.
# Gate 7: network-transport smoke — the process-actor pipeline on the
#         TCP experience backend (actor.transport=tcp, loopback): every
#         non-shm worker contributes verified non-torn chunks to real
#         training steps, an injected partial frame is detected as torn
#         and never ingested, the displaced worker reconnects and
#         resumes, a SIGKILLed worker respawns onto a fresh connection,
#         and param fan-out cost is recorded per push; then the
#         wire-efficiency leg — net_codec=zlib + coalescing + frame
#         dedup through a hello-negotiated connection into pool.poll,
#         asserting BIT-EXACT ingest and wire/logical < 1.0 with zero
#         torn frames (tools/net_smoke.py).
# Gate 8: serving-net smoke — the network serving tier end to end: a
#         2-replica fleet on ephemeral ports (router + delta param hub),
#         a closed-loop client burst over real sockets, a hot param
#         reload fanned out as page-deltas MID-BURST, one replica
#         SIGKILLed mid-burst (drained, respawned, full-synced), zero
#         dropped requests and fresh param_version on both replicas
#         (tools/serving_net_smoke.py).
# Gate 9: replay-service smoke — replay as a service end to end: a
#         2-shard replay fleet (own processes, own checkpoint chains),
#         TWO CLI learners attached over framed RPC, a remote worker
#         host joined via tools/host_join.py, one shard SIGKILLed
#         mid-run by the seeded kill-shard-at-step drill; both learners
#         must keep training through the outage (typed degradation,
#         buffered priority write-backs), the respawned shard must
#         recover bit-exact-or-typed from its chain (digest-verified
#         against the frozen chain), write-backs must flush, and no
#         torn frame may appear on either side
#         (tools/replay_svc_smoke.py).
# Gate 10: central-inference smoke — the SEED-style production story end
#         to end: a 2-replica routed serving fleet (serve.py children
#         with the trainer's --run-token), a process-actor trainer whose
#         workers are PARAMLESS (actor.inference=central, every action
#         selected through the router into a replica's micro-batcher,
#         ε worker-side on the global ladder slice), trainer publishes
#         fanned to the fleet as page-deltas, one replica SIGKILLed
#         mid-run; training must reach its step target with zero torn
#         frames on either side, zero worker deaths, fresh
#         param_version in replies, and the replica respawned
#         (tools/central_inference_smoke.py).
# Gate 11: fleet-observability smoke — the rollup plane end to end: a
#         trainer attached to a 2-shard replay fleet (full tracing) +
#         a 2-replica routed serving fleet, a FleetAggregator scraping
#         all five endpoints into one rollup (histograms merged across
#         shards AND replicas, a >=3-pid cross-tier trace timeline),
#         one shard SIGKILLed mid-run: the endpoint-liveness SLO must
#         fire a damped slo_breach, the shard must respawn, and
#         slo_clear must follow — with the rollup serving throughout
#         (tools/fleet_obs_smoke.py).
# Gate 12: elastic-autopilot smoke — ROADMAP item 3's done-condition,
#         CI-sized: an in-process trainer (process actors under slow-env
#         chaos, autopilot enabled) next to a 1-replica serving fleet
#         with sleep-bound service time, driven by a loadgen QPS step
#         schedule.  The controller must decide NOTHING while every SLO
#         is green; under the surge it must spawn replica 2 (one step,
#         busy-held) and the windowed serving p99 must re-hold; in the
#         idle phase it must retire the replica on the zero-drop drain
#         path (zero loadgen timeouts/errors across the run); and after
#         kill-half-the-workers quarantines a wid, it must grow the
#         reserved wid on the same ε-ladder partition until the windowed
#         age-of-experience p95 re-holds (tools/autopilot_smoke.py).
# Gate 13: elastic-replay smoke — the replay service as the third
#         autopilot-governed fleet, on the fleet discovery plane: a
#         standalone membership registry, a 2-shard replay fleet that
#         ANNOUNCES every shard, a from_registry client and a
#         bind_registry aggregator (no endpoints file in the driver
#         anywhere).  At the 2-shard floor the idle impulse must be
#         suppressed at_min with zero decisions; under ingest pressure
#         the per-shard add-QPS SLO must breach and the autopilot must
#         grow 2->3 (membership propagating the new shard to client and
#         sensor); when ingest stops, the idle burn window must retire
#         the shard through the digest-proven drain -> fingerprint ->
#         restore -> prove -> re-add handoff with ZERO lost transitions,
#         the client sampling throughout (tools/elastic_replay_smoke.py).
# Gate 14: apexlint — the repo's static invariant checkers
#         (ape_x_dqn_tpu/analysis/ + tools/lint.py; docs/INVARIANTS.md):
#         import-lightness of the no-jax child modules, the wire
#         kind/magic registry, config coverage, metrics-doc coverage,
#         shm discipline, typed-error discipline.  Purely static (~2 s;
#         hard budget 20 s), fails on any finding NEW relative to the
#         committed baseline.
# Gate 15: the tests, by the command the driver runs after every PR, as
#         the `commands` of its /root/TESTS_LAST_RUN.json give it (six
#         xdist workers by file, a 1,470 s limit, passes counted from the
#         junit file), but for its ALLOW_MULTIPLE_LIBTPU_LOAD=1, which no
#         file of the repository sets.  ROADMAP.md's "Tier-1 verify" line is
#         the driver's to change and still has the older one-process form;
#         where the two differ, the driver's file is right.
cd "$(dirname "$0")/.." || exit 1
timeout -k 10 120 python -m compileall -q ape_x_dqn_tpu tools || exit 1
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/xp_transport.py --smoke > /tmp/_t1_xp.log 2>&1 || { echo "xp_transport smoke FAILED:"; cat /tmp/_t1_xp.log; exit 1; }
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/ckpt_smoke.py > /tmp/_t1_ckpt.log 2>&1 || { echo "checkpoint smoke FAILED:"; cat /tmp/_t1_ckpt.log; exit 1; }
timeout -k 10 480 env JAX_PLATFORMS=cpu python tools/obs_smoke.py > /tmp/_t1_obs.log 2>&1 || { echo "obs smoke FAILED:"; cat /tmp/_t1_obs.log; exit 1; }
timeout -k 10 480 env JAX_PLATFORMS=cpu python tools/chaos_smoke.py > /tmp/_t1_chaos.log 2>&1 || { echo "chaos smoke FAILED:"; cat /tmp/_t1_chaos.log; exit 1; }
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/spill_smoke.py > /tmp/_t1_spill.log 2>&1 || { echo "spill smoke FAILED:"; cat /tmp/_t1_spill.log; exit 1; }
timeout -k 10 480 env JAX_PLATFORMS=cpu python tools/net_smoke.py > /tmp/_t1_net.log 2>&1 || { echo "net smoke FAILED:"; cat /tmp/_t1_net.log; exit 1; }
timeout -k 10 480 env JAX_PLATFORMS=cpu python tools/serving_net_smoke.py > /tmp/_t1_snet.log 2>&1 || { echo "serving-net smoke FAILED:"; cat /tmp/_t1_snet.log; exit 1; }
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/replay_svc_smoke.py > /tmp/_t1_rsvc.log 2>&1 || { echo "replay-svc smoke FAILED:"; cat /tmp/_t1_rsvc.log; exit 1; }
timeout -k 10 480 env JAX_PLATFORMS=cpu python tools/central_inference_smoke.py > /tmp/_t1_central.log 2>&1 || { echo "central-inference smoke FAILED:"; cat /tmp/_t1_central.log; exit 1; }
timeout -k 10 480 env JAX_PLATFORMS=cpu python tools/fleet_obs_smoke.py > /tmp/_t1_fleet.log 2>&1 || { echo "fleet-obs smoke FAILED:"; cat /tmp/_t1_fleet.log; exit 1; }
timeout -k 10 500 env JAX_PLATFORMS=cpu python tools/autopilot_smoke.py > /tmp/_t1_autopilot.log 2>&1 || { echo "autopilot smoke FAILED:"; cat /tmp/_t1_autopilot.log; exit 1; }
timeout -k 10 320 python tools/elastic_replay_smoke.py > /tmp/_t1_ereplay.log 2>&1 || { echo "elastic-replay smoke FAILED:"; cat /tmp/_t1_ereplay.log; exit 1; }
timeout -k 5 20 python -m tools.lint --fail-on-new > /tmp/_t1_lint.log 2>&1 || { echo "apexlint gate FAILED:"; cat /tmp/_t1_lint.log; exit 1; }
set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $rc
