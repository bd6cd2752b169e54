"""Price the replay-service RPC plane against in-process replay — the
number ROADMAP item 1 asked for: what does moving the replay out of the
learner's address space cost per sampled batch?

Three legs, same workload (Atari-shaped 84x84x1 uint8 frames, batch-32
sample + priority write-back per iteration, warm buffer):

  * ``in_process`` — PrioritizedReplay in this process (the baseline
    every learner ran before replay-as-a-service);
  * ``rpc_1shard`` — the same replay behind one ReplayShardServer
    SUBPROCESS on loopback (framed RPC, dedup+zlib bodies): the full
    serialization + syscall + scheduling cost of the service;
  * ``rpc_2shard`` — two shards (the fleet shape), mass-weighted shard
    choice per sample.

On a 1-core host the RPC legs price CPU (serialize/deflate/copy), not
network — the same caveat the xp_net bench carries.  Output: one JSON
line (committed as demos/replay_svc.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np  # noqa: E402


def _fill(target, rng, rows, obs_shape, chunk=256):
    class B:
        pass

    added = 0
    while added < rows:
        n = min(chunk, rows - added)
        b = B()
        obs = rng.integers(0, 255, (n, *obs_shape), dtype=np.uint8)
        b.obs = obs
        # n-step-overlap shape so the dedup layer sees production
        # redundancy on the add path.
        b.next_obs = np.roll(obs, -1, axis=0)
        b.action = rng.integers(0, 4, n).astype(np.int32)
        b.reward = rng.normal(size=n).astype(np.float32)
        b.discount = np.full(n, 0.99, np.float32)
        target.add((np.abs(rng.normal(size=n)) + 0.1).astype(np.float64), b)
        added += n


def _measure(target, rng, iters, batch):
    lat = []
    t0 = time.perf_counter()
    for _ in range(iters):
        t1 = time.perf_counter()
        b = target.sample(batch, beta=0.4, rng=rng)
        target.update_priorities(
            b.indices, np.abs(rng.normal(size=batch)) + 0.1
        )
        lat.append(time.perf_counter() - t1)
    wall = time.perf_counter() - t0
    lat_ms = np.asarray(sorted(lat)) * 1e3
    return {
        "iters": iters,
        "batch": batch,
        "samples_per_s": round(iters * batch / wall, 1),
        "ms_per_iter_p50": round(float(lat_ms[len(lat_ms) // 2]), 3),
        "ms_per_iter_p95": round(float(lat_ms[int(0.95 * len(lat_ms))]), 3),
        "wall_s": round(wall, 2),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="replay_svc_bench")
    ap.add_argument("--capacity", type=int, default=16_384)
    ap.add_argument("--rows", type=int, default=8_192)
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--obs-shape", default="84,84,1")
    ap.add_argument("--out", default="-")
    args = ap.parse_args(argv)

    from ape_x_dqn_tpu.replay.buffer import PrioritizedReplay
    from ape_x_dqn_tpu.replay.service import (
        ReplayServiceFleet,
        ShardClient,
        ShardedReplayClient,
    )

    obs_shape = tuple(int(d) for d in args.obs_shape.split(","))
    report = {
        "config": {"capacity": args.capacity, "rows": args.rows,
                   "iters": args.iters, "batch": args.batch,
                   "obs_shape": list(obs_shape)},
    }

    # Leg 1: in-process baseline.
    rep = PrioritizedReplay(args.capacity, obs_shape)
    rng = np.random.default_rng(0)
    _fill(rep, rng, args.rows, obs_shape)
    report["in_process"] = _measure(rep, rng, args.iters, args.batch)
    del rep

    # RPC legs: the service, shards as real subprocesses on loopback.
    # codec=off and codec=zlib are separate legs on purpose: these
    # RANDOM frames are incompressible, so the zlib leg prices the
    # worst-case codec CPU (deflate tried, discarded as not-smaller on
    # replies; the dedup layer still wins on the overlapping add path)
    # while the off leg prices pure framing+copy+syscall.
    # codec=auto is the PR-12 gate: the hello still negotiates the zlib
    # CAPABILITY, but the shard compresses sample replies only while its
    # reply sends observe kernel-buffer backpressure — on an unloaded
    # loopback it should price like the off leg, not the zlib one.
    for shards, codec in ((1, "off"), (1, "zlib"), (1, "auto"), (2, "off")):
        leg_name = f"rpc_{shards}shard" + (
            f"_{codec}" if codec != "off" else ""
        )
        root = tempfile.mkdtemp(prefix=f"rsvc-bench-{shards}{codec}-")
        fleet = ReplayServiceFleet(
            shards, args.capacity, obs_shape, root_dir=root, codec=codec,
            save_every_s=0.0,      # pure serving cost: no ckpt traffic
        )
        fleet.start(timeout=60.0)
        cl = ShardedReplayClient.from_endpoints_file(
            fleet.endpoints_path, request_timeout_s=30.0,
        )
        try:
            rng = np.random.default_rng(0)
            _fill(cl, rng, args.rows, obs_shape)
            leg = _measure(cl, rng, args.iters, args.batch)
            # Wire economy on the RPC plane (shard-side accounting).
            wire = logical = zlib_n = raw_n = fw = 0
            for s in fleet.shards:
                sc = ShardClient(s.shard_id, "127.0.0.1", s.port,
                                 token=fleet.token, client_id=77,
                                 incarnation=s.incarnation, codec=codec)
                st = sc.shard_stats(timeout=10.0)
                wire += st["bytes_in"]
                logical += st["logical_bytes_in"]
                zlib_n += st.get("reply_zlib", 0)
                raw_n += st.get("reply_raw", 0)
                fw += st.get("reply_full_waits", 0)
                sc.close()
            leg["add_wire_over_logical"] = (
                round(wire / logical, 4) if logical else None
            )
            leg["codec"] = codec
            leg["reply_zlib"] = zlib_n
            leg["reply_raw"] = raw_n
            leg["reply_full_waits"] = fw
            report[leg_name] = leg
        finally:
            cl.close()
            fleet.stop()

    base = report["in_process"]["samples_per_s"]
    for k in ("rpc_1shard", "rpc_1shard_zlib", "rpc_1shard_auto",
              "rpc_2shard"):
        if k in report and base:
            report[k]["vs_in_process"] = round(
                report[k]["samples_per_s"] / base, 3
            )
    report["note"] = (
        "loopback subprocess shards on a shared host: the RPC legs price "
        "serialize/deflate/syscall CPU, not network bytes; "
        "add_wire_over_logical shows the dedup+zlib body economy"
    )
    line = json.dumps(report)
    if args.out == "-":
        print(line)
    else:
        with open(args.out, "w") as f:
            f.write(line + "\n")
        print(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
