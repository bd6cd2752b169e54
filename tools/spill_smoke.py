#!/usr/bin/env python
"""Tiered-replay smoke — verify_t1.sh GATE 6 (ISSUE 7).

CI-sized proof of the cold tier's whole contract, in seconds:

  1. **Bit-exact under spill** — a DedupReplay with a hot budget small
     enough that most spans live cold must produce byte-identical sample
     batches (frames, indices, IS weights) to its dense twin under the
     same RNG, with evictions forced between every operation, and must
     actually have spilled and faulted (counters > 0).  The native core
     repeats the check when the toolchain allows.
  2. **Kill/restore** — a forked child ingests + spills + sync-saves an
     incremental chain until SIGKILLed mid-flight.  The parent restores
     the committed manifest (fallback on — a torn cold record walks the
     chain, never crashes the resume), verifies the restored state is
     BIT-EXACT against a dense twin fed the same deterministic schedule
     to the restored step, then trains past it (add + sample on the
     restored tiered replay).

Import-light on purpose: replay + checkpoint machinery only, no jax —
the gate runs in a couple of seconds.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import signal
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ape_x_dqn_tpu.replay.dedup import DedupReplay  # noqa: E402
from ape_x_dqn_tpu.types import DedupChunk  # noqa: E402
from ape_x_dqn_tpu.utils.checkpoint_inc import (  # noqa: E402
    IncrementalCheckpointer,
    inc_dir,
    load_incremental_replay,
    read_manifest,
)

OBS = (12, 12, 1)
CAP = 256
SPAN = 4
BUDGET = 8 * SPAN * int(np.prod(OBS))  # ~8 spans hot of 80 — mostly cold


def _chunk(seq: int, M: int = 16):
    r = np.random.default_rng(seq * 7919 + 1)
    return DedupChunk(
        frames=r.integers(0, 255, (M + 1, *OBS), dtype=np.uint8),
        obs_ref=np.arange(M, dtype=np.int32),
        next_ref=np.arange(1, M + 1, dtype=np.int32),
        action=r.integers(0, 4, M).astype(np.int32),
        reward=r.normal(size=M).astype(np.float32),
        discount=np.full(M, 0.97, np.float32),
        source=1, chunk_seq=seq, prev_frames=M + 1,
    )


def _prio(seq: int, M: int = 16):
    r = np.random.default_rng(seq + 5000)
    return (np.abs(r.normal(size=M)) + 0.1).astype(np.float32)


def _tiered(spill: str, budget: int = BUDGET) -> DedupReplay:
    return DedupReplay(CAP, OBS, hot_frame_budget_bytes=budget,
                       spill_dir=spill, spill_span_frames=SPAN)


def _feed(rep, k: int, spill_each: bool = False) -> None:
    rep.add(_prio(k), _chunk(k))
    if spill_each:
        rep.spill_cold()


def _phase_bit_exact(spill: str) -> dict:
    dense = DedupReplay(CAP, OBS)
    tiered = _tiered(spill)
    for k in range(24):  # wraps the ring
        _feed(dense, k)
        _feed(tiered, k, spill_each=True)
    batches = 0
    for k in range(16):
        ra = dense.sample(32, rng=np.random.default_rng(900 + k))
        rb = tiered.sample(32, rng=np.random.default_rng(900 + k))
        if not (np.array_equal(ra.indices, rb.indices)
                and np.array_equal(ra.is_weights, rb.is_weights)
                and np.array_equal(ra.transition.obs, rb.transition.obs)
                and np.array_equal(ra.transition.next_obs,
                                   rb.transition.next_obs)):
            raise AssertionError(f"tiered sample batch {k} != dense twin")
        up = _prio(3000 + k, 32)
        dense.update_priorities(ra.indices, up)
        tiered.update_priorities(rb.indices, up)
        tiered.spill_cold()
        batches += 1
    stats = tiered.tier_stats()
    assert stats["spill_writes"] > 0, "nothing spilled — budget too big?"
    assert stats["fault_reads"] > 0, "nothing faulted — tier never cold?"
    assert stats["hot_bytes"] <= BUDGET + stats["span_frames"] * int(
        np.prod(OBS)
    ), "hot tier exceeded its budget"
    out = {"batches_bit_exact": batches,
           "spill_writes": stats["spill_writes"],
           "fault_reads": stats["fault_reads"],
           "hot_bytes": stats["hot_bytes"]}
    # Native twin, when the toolchain allows (same contract, fused
    # two-phase C sampling).
    try:
        from ape_x_dqn_tpu.replay.native_dedup import (
            NativeDedupReplay,
            native_dedup_available,
        )

        if native_dedup_available():
            nat_spill = os.path.join(spill, "native")
            nd = NativeDedupReplay(CAP, OBS)
            nt = NativeDedupReplay(
                CAP, OBS, hot_frame_budget_bytes=BUDGET,
                spill_dir=nat_spill, spill_span_frames=SPAN,
            )
            for k in range(24):
                _feed(nd, k)
                _feed(nt, k, spill_each=True)
            for k in range(8):
                u = np.random.default_rng(700 + k).random(32)
                ra = nd._sample_with_uniforms(u.copy(), 0.4)
                rb = nt._sample_with_uniforms(u.copy(), 0.4)
                if not (np.array_equal(ra.indices, rb.indices)
                        and np.array_equal(ra.transition.obs,
                                           rb.transition.obs)):
                    raise AssertionError(
                        f"native tiered batch {k} != dense twin"
                    )
            out["native_checked"] = True
            out["native_fault_reads"] = nt.tier_stats()["fault_reads"]
    except ImportError:
        out["native_checked"] = False
    return out


def _kill_victim(root: str) -> None:
    """Ingest + spill + sync-save until SIGKILLed (deterministic feed:
    ingest-only, so the parent can rebuild the expected state)."""
    rep = _tiered(os.path.join(root, "spill"))
    ck = IncrementalCheckpointer(root, rep, sync=True, base_every=3)
    step = 0
    while True:
        _feed(rep, step, spill_each=True)
        step += 1
        ck.save(step)


def _phase_kill_restore(root: str, timeout_s: float) -> dict:
    ctx = multiprocessing.get_context("fork")
    proc = ctx.Process(target=_kill_victim, args=(root,), daemon=True)
    proc.start()
    try:
        deadline = time.monotonic() + timeout_s
        while True:
            m = read_manifest(inc_dir(root))
            if m is not None and m["step"] >= 3:
                break
            assert proc.is_alive(), "victim died on its own"
            assert time.monotonic() < deadline, "no committed save in time"
            time.sleep(0.01)
        time.sleep(0.05)  # land the kill mid-spill/mid-save
    finally:
        os.kill(proc.pid, signal.SIGKILL)
        proc.join(10.0)
    manifest = read_manifest(inc_dir(root))
    rep = _tiered(os.path.join(root, "spill"))
    step = load_incremental_replay(root, rep, fallback=True)
    assert step is not None and step >= 1, "no committed chain restored"
    # Bit-exact against the deterministic schedule replayed densely.
    twin = DedupReplay(CAP, OBS)
    for k in range(step):
        _feed(twin, k)
    want, got = twin.state_dict(), rep.state_dict()
    for key in want:
        if not np.array_equal(np.asarray(want[key]), np.asarray(got[key])):
            raise AssertionError(f"restored state differs at {key!r}")
    # Train past the restore: ingest + sample still serve on the
    # restored tiered replay.
    for k in range(step, step + 4):
        _feed(rep, k, spill_each=True)
    rep.sample(32, rng=np.random.default_rng(0))
    return {
        "committed_step": int(manifest["step"]),
        "restored_step": int(step),
        "continued_to_step": int(step) + 4,
        "restore_bit_exact": True,
    }


def run_smoke(workdir: str, timeout_s: float = 60.0) -> dict:
    os.makedirs(workdir, exist_ok=True)
    out = {"ok": False}
    out["bit_exact"] = _phase_bit_exact(os.path.join(workdir, "parity"))
    out["kill_restore"] = _phase_kill_restore(
        os.path.join(workdir, "chain"), timeout_s
    )
    out["ok"] = True
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--timeout", type=float, default=60.0)
    args = ap.parse_args()
    workdir = args.workdir or tempfile.mkdtemp(prefix="apex-spill-smoke-")
    try:
        out = run_smoke(workdir, timeout_s=args.timeout)
    except Exception as e:  # noqa: BLE001 — the gate reports one JSON line
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"}))
        return 1
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
