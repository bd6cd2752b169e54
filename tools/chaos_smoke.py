"""Chaos smoke gate (tools/verify_t1.sh gate 5): the fault-tolerance
contract, CI-sized.

One bounded pass (<60 s of run time on a healthy host) over the
supervision + chaos tier on the REAL process-actor pipeline:

  1. start the async pipeline (2 workers, host replay, incremental
     checkpointing, supervisor on, exporter on an ephemeral port);
  2. SIGKILL one worker — the supervisor must respawn it (backoff, not
     hot-loop) and count it on ``supervisor/respawns``;
  3. SIGKILL a second worker and inject a TORN ring record at its dead
     write cursor (obs/chaos.inject_torn_record) — salvage must count the
     torn tail and never deliver it to replay ingest;
  4. stop cleanly, flip one byte in the newest committed APXC chunk, and
     RESTORE: the resume must walk the chain back (fallback restore, a
     ``degraded_restore`` event + ``supervisor/fallback_restores`` >= 1)
     and train PAST the restored step;
  5. assert zero quarantines (the budget was never blown) and print a
     one-line JSON verdict.

    python tools/chaos_smoke.py
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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_cfg(ckpt_dir: str, workers: int, restore: bool = False):
    from ape_x_dqn_tpu.config import ApexConfig

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.mode = "process"
    cfg.actor.num_workers = workers
    cfg.actor.num_actors = 2 * workers
    cfg.actor.T = 10_000_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 32
    cfg.actor.respawn_min_interval_s = 0.1
    cfg.learner.min_replay_mem_size = 256
    cfg.learner.publish_every = 10
    cfg.learner.total_steps = 10**9
    cfg.learner.optimizer = "adam"
    cfg.learner.learning_rate = 1e-3
    cfg.learner.checkpoint_every = 20
    cfg.learner.checkpoint_dir = ckpt_dir
    cfg.learner.checkpoint_incremental = True
    cfg.learner.checkpoint_base_every = 2
    cfg.learner.restore_from = restore
    cfg.replay.capacity = 8192
    cfg.obs.export_port = 0
    # Fast supervision for a smoke: short backoffs, generous budget (the
    # gate asserts NO quarantine — two kills must stay well inside it).
    cfg.supervisor.respawn_backoff_base_s = 0.2
    cfg.supervisor.respawn_backoff_max_s = 2.0
    cfg.supervisor.crash_loop_budget = 5
    cfg.validate()
    return cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chaos_smoke")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--deadline", type=float, default=420.0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")

    from ape_x_dqn_tpu.obs.chaos import (
        corrupt_chunk,
        inject_torn_record,
        pick_chunk,
    )
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    tmp = tempfile.mkdtemp(prefix="chaos_smoke_")
    ckpt_dir = os.path.join(tmp, "ckpt")
    verdict: dict = {"ckpt_dir": ckpt_dir}
    deadline = time.monotonic() + args.deadline

    def wait_for(cond, what: str, poll=0.1):
        while time.monotonic() < deadline:
            if err:
                raise RuntimeError(f"pipeline died ({what}): {err[0]}")
            if cond():
                return
            time.sleep(poll)
        raise TimeoutError(f"deadline waiting for {what}")

    # ---- phase A: run under injected faults -----------------------------
    cfg = _make_cfg(ckpt_dir, args.workers)
    pipe = AsyncPipeline(
        cfg, logger=MetricLogger(stream=open(os.devnull, "w")),
        log_every=200,
    )
    err: list = []
    t = threading.Thread(
        target=lambda: _run(pipe, err), name="smoke-trainer", daemon=True
    )
    t.start()
    pool = pipe.worker.pool
    sup = pipe.supervisor
    assert sup is not None, "supervisor not built"
    inc_dir = os.path.join(ckpt_dir, "replay_inc")
    try:
        wait_for(lambda: pipe.learner_step > 0, "first learner step")

        # -- 2: plain SIGKILL -> supervised respawn ------------------------
        victim = pool._procs[0]
        os.kill(victim.pid, signal.SIGKILL)
        verdict["kill_1"] = {"worker": 0, "pid": victim.pid}
        wait_for(lambda: sup.respawns.value >= 1, "supervised respawn")

        # -- 3: SIGKILL + torn ring record -> salvaged, never ingested -----
        wait_for(lambda: pool._procs[1].is_alive()
                 and 1 in pool.last_versions, "worker 1 feeding")
        victim = pool._procs[1]
        os.kill(victim.pid, signal.SIGKILL)
        victim.join(timeout=30.0)
        inject_torn_record(pool._rings[1])
        verdict["kill_2_torn"] = {"worker": 1, "pid": victim.pid}
        wait_for(lambda: pool.transport.torn_records >= 1,
                 "torn tail counted at salvage")
        wait_for(lambda: sup.respawns.value >= 2, "second respawn")

        # -- chain committed deep enough to walk back ----------------------
        def chunks_committed():
            from ape_x_dqn_tpu.utils.checkpoint_inc import read_manifest

            m = read_manifest(inc_dir)
            return m is not None and len(m["chunks"]) >= 2
        wait_for(chunks_committed, "committed base+delta chain")
        step_a = pipe.learner_step
    finally:
        pipe.stop_event.set()
        t.join(timeout=120.0)
    if err:
        verdict["phase_a_error"] = err[0]
        print(json.dumps(verdict))
        return 1
    verdict["phase_a"] = {
        "end_step": step_a,
        "respawns": int(sup.respawns.value),
        "quarantines": int(sup.quarantines.value),
        "torn_salvaged": int(pool.transport.torn_records),
        "salvaged_records": int(pool.transport.salvaged_records),
    }
    assert sup.quarantines.value == 0, "budget blown in a 2-kill smoke"

    # ---- 4: corrupt the newest committed chunk, restore through it ------
    bad = pick_chunk(inc_dir, prefer="delta") or pick_chunk(inc_dir)
    assert bad, "no committed chunk to corrupt"
    verdict["corrupted"] = corrupt_chunk(bad, "bitflip")
    cfg_b = _make_cfg(ckpt_dir, args.workers, restore=True)
    pipe_b = AsyncPipeline(
        cfg_b, logger=MetricLogger(stream=open(os.devnull, "w")),
        log_every=200,
    )
    fb = int(pipe_b.supervisor.fallback_restores.value)
    assert fb >= 1, "corrupt chunk did not surface as a fallback restore"
    resumed = pipe_b.learner_step
    assert resumed > 0, "state did not restore"
    assert pipe_b.comps.replay.size() > 0, "replay came back empty"
    result = pipe_b.run(learner_steps=resumed + 30, warmup_timeout=240.0)
    assert result["step"] >= resumed + 30, result["step"]
    verdict["phase_b"] = {
        "resumed_step": resumed,
        "fallback_restores": fb,
        "replay_size_at_restore": int(result["replay_size"]),
        "continued_to_step": int(result["step"]),
        "supervisor_record": result.get("supervisor"),
    }
    verdict["ok"] = True
    print(json.dumps(verdict))
    return 0


def _run(pipe, err: list) -> None:
    try:
        pipe.run(warmup_timeout=300.0)
    except Exception as e:  # noqa: BLE001 — surfaced in the verdict
        err.append(f"{type(e).__name__}: {e}")


if __name__ == "__main__":
    raise SystemExit(main())
