"""Network-transport smoke gate (tools/verify_t1.sh gate 7).

The TCP experience transport's end-to-end contract, CI-sized, on the
REAL process-actor pipeline (actor.transport=tcp, loopback):

  1. start the async pipeline with every worker feeding the learner over
     a TCP connection instead of a shm ring — remote-worker flavor on
     loopback — and assert non-shm workers contribute verified,
     non-torn chunks to real training steps (learner progresses, frames
     flow, torn count zero);
  2. DETERMINISTIC torn frame: hijack a live worker's channel with a raw
     socket (valid hello — same wid/attempt/token), send a partial frame
     (length prefix promising more bytes than delivered) and disconnect.
     The channel must count a torn frame, ingest NOTHING from it, and
     the displaced real worker must reconnect-with-backoff and keep
     contributing (the stream-level twin of the torn-ring-tail salvage
     rule);
  3. SIGKILL a worker mid-stream: the pool respawns it, the fresh
     incarnation reconnects, and its chunks flow again;
  4. param fan-out over the same connections: published versions reach
     workers (param_version advances in worker stats), with per-push
     fan-out cost recorded on the `net` section;
  5. WIRE-EFFICIENCY leg (ISSUE 10): the same pool transport surface
     with `net_codec=zlib` + coalescing + frame dedup on — deterministic
     trajectory chunks through a real NetWriter → hello-negotiated
     connection → pool.poll, asserting BIT-EXACT ingest (every decoded
     array equals its source) and a measured wire/logical ratio < 1.0,
     with zero torn frames.  Runs in-process in ~a second (no extra jax
     children), so the gate's time budget stands;
  6. stop cleanly; print a one-line JSON verdict.

    python tools/net_smoke.py
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import struct
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="net_smoke")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--deadline", type=float, default=420.0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")

    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
    from ape_x_dqn_tpu.runtime.net import _HELLO, _NET_MAGIC, _NET_VERSION
    from ape_x_dqn_tpu.utils.metrics import MetricLogger

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.mode = "process"
    cfg.actor.transport = "tcp"
    cfg.actor.num_workers = args.workers
    cfg.actor.num_actors = 2 * args.workers
    cfg.actor.T = 10_000_000
    cfg.actor.flush_every = 8
    cfg.actor.sync_every = 32
    cfg.learner.min_replay_mem_size = 256
    cfg.learner.publish_every = 10
    cfg.learner.total_steps = 10**9
    cfg.learner.optimizer = "adam"
    cfg.learner.learning_rate = 1e-3
    cfg.replay.capacity = 8192
    cfg.obs.trace_sample_rate = 1.0
    cfg.obs.postmortem_dir = None
    cfg.validate()

    logger = MetricLogger(stream=open(os.devnull, "w"))
    pipe = AsyncPipeline(cfg, logger=logger, log_every=200)
    pool = pipe.worker.pool
    assert pool.transport_kind == "tcp"
    verdict: dict = {"workers": args.workers,
                     "port": pool._transport.port}
    err: list = []
    t = threading.Thread(
        target=lambda: _run(pipe, err), name="smoke-trainer", daemon=True
    )
    t.start()
    deadline = time.monotonic() + args.deadline

    def wait_for(cond, label):
        while time.monotonic() < deadline:
            if err:
                raise RuntimeError(f"pipeline died during {label}: {err[0]}")
            if cond():
                return
            time.sleep(0.25)
        raise TimeoutError(f"{label} did not happen in time")

    try:
        # -- 1: every non-shm worker contributes to real training ----------
        all_wids = set(range(args.workers))
        wait_for(
            lambda: set(pool.last_versions) == all_wids
            and pipe.learner_step > 0,
            "tcp-chunks-from-every-worker-into-training",
        )
        net = pool.net_stats()
        assert net["connections"] == args.workers, net
        assert net["frames_in"] > 0 and net["torn_frames"] == 0, net
        verdict["step_at_flow"] = pipe.learner_step
        verdict["frames_at_flow"] = net["frames_in"]

        # -- 2: deterministic torn frame via channel hijack ----------------
        tr = pool._transport.net
        attempt0 = pool._attempt[0] - 1
        raw = socket.create_connection(("127.0.0.1", tr.port), timeout=5)
        raw.sendall(_HELLO.pack(_NET_MAGIC, _NET_VERSION, 0, attempt0,
                                tr.token))
        # A frame header promising 4096 payload bytes, 100 delivered.
        raw.sendall(struct.pack("<IIqB7x", 4096, 0xDEAD, 1, 1) + b"x" * 100)
        time.sleep(0.3)
        raw.close()
        records_before = pool.transport.chunks
        wait_for(lambda: pool.net_stats()["torn_frames"] >= 1,
                 "torn-frame-detected")
        # The garbage never ingested: the torn stream contributed zero
        # records (any records since the hijack are from live workers'
        # verified frames — training stays healthy below).
        wait_for(lambda: pool.net_stats()["reconnects"] >= 1,
                 "displaced-worker-reconnects")
        frames0 = pool.net_stats()["frames_in"]
        wait_for(lambda: pool.net_stats()["frames_in"] > frames0,
                 "experience-resumes-after-reconnect")
        verdict["torn_frames"] = pool.net_stats()["torn_frames"]
        verdict["reconnects"] = pool.net_stats()["reconnects"]
        verdict["records_since_hijack"] = (
            pool.transport.chunks - records_before
        )

        # -- 3: SIGKILL mid-stream -> respawn -> fresh connection ----------
        victim = 1 if args.workers > 1 else 0
        steps_before = pool._steps_by_worker.get(victim, 0)
        os.kill(pool._procs[victim].pid, signal.SIGKILL)
        wait_for(
            lambda: pool._steps_by_worker.get(victim, 0) > steps_before
            and pool.restarts >= 1,
            "respawn-and-resume-after-sigkill",
        )
        verdict["restarts"] = pool.restarts

        # -- 4: param fan-out cost recorded --------------------------------
        net = pool.net_stats()
        assert net["param_pushes"] >= 1 and net["param_bytes"] > 0, net
        assert net["param_fanout_ms_last"] is not None, net
        verdict["param"] = {
            k: net[k] for k in ("param_pushes", "param_full", "param_delta",
                                "param_bytes", "param_fanout_ms_last")
        }
        # Workers actually hold published versions (the subscription is
        # live, not just counted).
        wait_for(
            lambda: any(
                w.get("param_version", 0) > 0
                for w in pool.worker_stats(max_age_s=0.0).values()
            ),
            "workers-hold-published-params",
        )
        # Lineage closes the loop: a traced tcp chunk reached a train
        # step (act -> ingest -> sample -> trained), and loopback stamps
        # never tripped the cross-host clock guard.
        wait_for(lambda: pipe._lineage.completed_count > 0,
                 "lineage-span-through-tcp-chunks")
        assert pipe._lineage.clock_skew_clamped == 0
        verdict["lineage_spans"] = pipe._lineage.completed_count

        # -- 5: wire-efficiency leg (codec + coalesce + dedup) -------------
        verdict["wire_leg"] = _wire_leg()
        verdict["ok"] = True
    finally:
        pipe.stop_event.set()
        t.join(timeout=120.0)
    if err:
        verdict["run_error"] = err[0]
    print(json.dumps(verdict))
    return 0 if verdict.get("ok") else 1


def _wire_leg() -> dict:
    """net_codec=zlib + coalescing + frame dedup on the pool's transport
    surface: deterministic trajectory chunks (production n-step overlap)
    through a real hello-negotiated connection into pool.poll — BIT-EXACT
    ingest, wire/logical < 1.0, zero torn frames."""
    import numpy as np

    from ape_x_dqn_tpu.config import ApexConfig
    from ape_x_dqn_tpu.runtime.process_actors import ProcessActorPool
    from ape_x_dqn_tpu.runtime.shm_ring import XP, encode_chunk_parts
    from ape_x_dqn_tpu.runtime.transport import connect_channel

    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.mode = "process"
    cfg.actor.transport = "tcp"
    cfg.actor.net_codec = "zlib"
    cfg.actor.net_coalesce_bytes = 1 << 20
    cfg.actor.num_workers = 1
    cfg.actor.num_actors = 2
    cfg.obs.postmortem_dir = None
    cfg.validate()
    pool = ProcessActorPool(cfg, num_workers=1, ring_bytes=1 << 16)
    try:
        pool._queues[0] = pool._ctx.Queue(maxsize=4)
        pool._rings[0] = pool._transport.make_channel(0, 0)
        spec = pool._transport.endpoint(pool._rings[0], 0, 0)
        assert spec["codec"] == "zlib" and spec["coalesce"] == 1 << 20
        w = connect_channel(spec)
        rng = np.random.default_rng(5)
        rows, n = 16, 3
        # Trajectory-shaped frames: static background + moving sprite,
        # obs[i + n] == next_obs[i] — what the dedup window removes.
        stream = np.repeat(
            rng.integers(0, 255, (1, 24, 24, 1), dtype=np.uint8),
            3 * rows + n, axis=0,
        )
        for i in range(stream.shape[0]):
            y = (3 * i) % 16
            stream[i, y:y + 8, :8] = rng.integers(
                0, 255, (8, 8, 1), dtype=np.uint8
            )
        sent = []
        for c in range(3):
            arrays = {
                "prio": (np.abs(rng.normal(size=rows)) + 0.1).astype(
                    np.float32
                ),
                "obs": np.ascontiguousarray(
                    stream[c * rows:c * rows + rows]
                ),
                "action": rng.integers(0, 4, (rows,), dtype=np.int32),
                "reward": rng.normal(size=(rows,)).astype(np.float32),
                "discount": np.full((rows,), 0.97, np.float32),
                "next_obs": np.ascontiguousarray(
                    stream[c * rows + n:c * rows + rows + n]
                ),
            }
            sent.append(arrays)
            assert w.write(
                encode_chunk_parts(XP, 30 + c, rows, arrays), timeout=10
            )
        assert w.flush(timeout=10)
        items = []
        deadline = time.monotonic() + 30
        while len(items) < 3 and time.monotonic() < deadline:
            items.extend(pool.poll(max_items=8))
            time.sleep(0.01)
        assert len(items) == 3, f"only {len(items)}/3 chunks ingested"
        for (prio, trans), arrays in zip(items, sent):
            # Bit-exact ingest: every decoded array equals its source.
            np.testing.assert_array_equal(prio, arrays["prio"])
            for field in ("obs", "action", "reward", "discount",
                          "next_obs"):
                np.testing.assert_array_equal(
                    getattr(trans, field), arrays[field]
                )
        net = pool.net_stats()
        assert net["torn_frames"] == 0, net
        assert net["frames_in"] == 3, net
        assert net["coalesced_frames_in"] >= 1, net
        assert net["wire_over_logical"] is not None
        assert net["wire_over_logical"] < 1.0, net
        w.close()
        return {
            "bit_exact_chunks": 3,
            "wire_over_logical": net["wire_over_logical"],
            "records_per_frame": net["records_per_frame"],
            "codec_frames_in": net["codec_frames_in"],
        }
    finally:
        pool.stop(join_timeout=5.0)


def _run(pipe, err: list) -> None:
    try:
        pipe.run(warmup_timeout=300.0)
    except Exception as e:  # noqa: BLE001 — surfaced in the verdict
        err.append(f"{type(e).__name__}: {e}")


if __name__ == "__main__":
    raise SystemExit(main())
