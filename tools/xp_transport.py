"""Experience-transport microbench: shm ring vs pickle-over-mp.Queue.

Measures the actor→learner chunk path in isolation — N producer processes
pushing realistic experience chunks at one consumer — for both transports:

  * ``mp_queue``: the pre-ring production path verbatim (one bounded
    ``mp.Queue`` per worker, chunks as pickled numpy dicts).
  * ``shm_ring``: one ``runtime/shm_ring.ShmRing`` per worker, chunks in
    the APXT wire format gathered straight into shared memory.

Also runs the SIGKILL barrage: ring producers killed at random moments
mid-stream, then a full salvage — proving zero fully-committed chunks are
lost and torn tails are detected (the property the transport exists for).

This module is deliberately import-light (stdlib + numpy): producer
children and the bench driver load ``shm_ring.py`` BY FILE PATH instead of
through the package, so no child ever pays the package's jax import — the
section is host-only: no process in it initialises a backend.
"""

from __future__ import annotations

import importlib.util
import os
import queue as queue_mod
import signal
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

_RUNTIME_DIR = os.path.abspath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir,
    "ape_x_dqn_tpu", "runtime",
))
_SHM_RING_PATH = os.path.join(_RUNTIME_DIR, "shm_ring.py")
_NET_PATH = os.path.join(_RUNTIME_DIR, "net.py")


def _load_by_path(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_shm_ring():
    """shm_ring as a standalone module (no package import, no jax)."""
    return _load_by_path("_apex_shm_ring", _SHM_RING_PATH)


def load_net():
    """net as a standalone module (no package import, no jax)."""
    return _load_by_path("_apex_net", _NET_PATH)


def _make_arrays(wid: int, rows: int, obs_shape) -> Dict[str, np.ndarray]:
    """One dense experience chunk's arrays, production-shaped (the xp wire
    dict: priorities + the five NStepTransition fields)."""
    rng = np.random.default_rng(wid)
    return {
        "prio": (np.abs(rng.normal(size=rows)) + 0.1).astype(np.float32),
        "obs": rng.integers(0, 255, (rows, *obs_shape), dtype=np.uint8),
        "action": rng.integers(0, 4, (rows,), dtype=np.int32),
        "reward": rng.normal(size=(rows,)).astype(np.float32),
        "discount": np.full((rows,), 0.97, np.float32),
        "next_obs": rng.integers(0, 255, (rows, *obs_shape), dtype=np.uint8),
    }


class _TrajChunker:
    """TRAJECTORY-shaped chunk source: one continuing frame stream per
    producer with the production n-step overlap (``obs[i + n] ==
    next_obs[i]`` — the ~2x frame redundancy the replay dedup tier
    measures at emission ratio ~1.02) and Atari-like content (static
    background + a small moving sprite), so wire dedup/compression
    measure what they would see from real actors instead of the
    incompressible iid noise of ``_make_arrays`` (kept for the
    shm-vs-queue section, where content cannot matter: every transport
    memcpys the same byte count).  Each ``next()`` ADVANCES the stream —
    consecutive chunks share only the n-step boundary frames, never
    whole bodies — over a precomputed cycle long enough that no
    coalescing window ever sees the same stream position twice."""

    CYCLE = 509                 # prime >> any coalescing window, in frames

    def __init__(self, wid: int, rows: int, obs_shape, n_step: int = 3):
        rng = np.random.default_rng(wid)
        self._rng = rng
        self._rows = rows
        self._n = n_step
        h = int(obs_shape[0])
        w = int(obs_shape[1]) if len(obs_shape) > 1 else 1
        base = rng.integers(0, 255, obs_shape, dtype=np.uint8)
        self._frames = np.repeat(base[None], self.CYCLE, axis=0)
        sp = max(2, min(8, h // 4))
        for i in range(self.CYCLE):         # the sprite walks the frame
            y = (3 * i) % max(1, h - sp)
            x = (5 * i) % max(1, w - sp)
            self._frames[i, y:y + sp, x:x + sp] = rng.integers(
                0, 255, self._frames[i, y:y + sp, x:x + sp].shape,
                dtype=np.uint8,
            )
        self._pos = 0

    def next(self) -> Dict[str, np.ndarray]:
        rows, n, rng = self._rows, self._n, self._rng
        idx = (self._pos + np.arange(rows + n)) % self.CYCLE
        window = self._frames.take(idx, axis=0)   # fresh gather per chunk
        self._pos = (self._pos + rows) % self.CYCLE
        return {
            "prio": (np.abs(rng.normal(size=rows)) + 0.1).astype(
                np.float32
            ),
            "obs": np.ascontiguousarray(window[:rows]),
            "action": rng.integers(0, 4, (rows,), dtype=np.int32),
            "reward": rng.normal(size=(rows,)).astype(np.float32),
            "discount": np.full((rows,), 0.97, np.float32),
            "next_obs": np.ascontiguousarray(window[n:]),
        }


def _nice(n: int) -> None:
    """Production parity: worker processes run niced so the learner-side
    drain thread stays scheduled (config.ActorConfig.worker_nice) —
    applied identically to BOTH transports' producers."""
    try:
        os.nice(n)
    except OSError:
        pass


def _queue_producer(q, wid: int, rows: int, obs_shape, stop_evt,
                    nice: int = 10) -> None:
    """The pre-ring production put, verbatim shape: pickle through a
    bounded mp.Queue."""
    _nice(nice)
    arrays = _make_arrays(wid, rows, obs_shape)
    prio = arrays["prio"]
    tdict = {k: v for k, v in arrays.items() if k != "prio"}
    seq = 0
    while not stop_evt.is_set():
        try:
            q.put(("xp", wid, seq, prio, tdict, rows), timeout=0.1)
            seq += 1
        except queue_mod.Full:
            continue


def _ring_producer(ring_name: str, capacity: int, wid: int, rows: int,
                   obs_shape, stop_evt, nice: int = 10,
                   traj: bool = False) -> None:
    """Chunks into the shm ring, the production encode path (version field
    carries the chunk seq so the barrage can validate per-chunk identity)."""
    _nice(nice)
    mod = load_shm_ring()
    ring = mod.ShmRing(capacity, name=ring_name, create=False)
    chunker = _TrajChunker(wid, rows, obs_shape) if traj else None
    arrays = _make_arrays(wid, rows, obs_shape) if not traj else None
    seq = 0
    try:
        while not stop_evt.is_set():
            if chunker is not None:
                arrays = chunker.next()
            parts = mod.encode_chunk_parts(mod.XP, seq, rows, arrays)
            if not ring.write(parts, should_stop=stop_evt.is_set):
                break
            seq += 1
    finally:
        ring.close()


def _net_producer(host: str, port: int, token: int, wid: int, rows: int,
                  obs_shape, stop_evt, nice: int = 10,
                  traj: bool = False, wire: Optional[dict] = None) -> None:
    """Chunks over the TCP transport (runtime/net.py loaded by path),
    the production encode path — byte-identical frames to what a remote
    worker on another host would send.  ``wire`` carries the
    wire-efficiency spec fields (codec/coalesce/dedup); None keeps the
    v1 one-frame-per-record wire."""
    _nice(nice)
    ring_mod = load_shm_ring()
    net_mod = load_net()
    spec = {"host": host, "port": port, "token": token,
            "wid": wid, "attempt": 0}
    if wire:
        spec.update(wire)
    w = net_mod.NetWriter(spec)
    chunker = _TrajChunker(wid, rows, obs_shape) if traj else None
    arrays = _make_arrays(wid, rows, obs_shape) if not traj else None
    seq = 0
    try:
        while not stop_evt.is_set():
            if chunker is not None:
                arrays = chunker.next()
            parts = ring_mod.encode_chunk_parts(ring_mod.XP, seq, rows,
                                                arrays)
            if not w.write(parts, should_stop=stop_evt.is_set):
                break
            seq += 1
    finally:
        w.close()


def _spawn_all(ctx, target, argss):
    procs = []
    for args in argss:
        p = ctx.Process(target=target, args=args, daemon=True)
        p.start()
        procs.append(p)
    return procs


def run_transport_point(transport: str, workers: int, seconds: float,
                        rows: int = 64, obs_shape=(84, 84, 1),
                        ring_bytes: int = 4 << 20,
                        ready_timeout: float = 180.0,
                        traj: bool = False,
                        wire: Optional[dict] = None) -> dict:
    """One load point: ``workers`` producers → one consumer for a timed
    window.  The window starts only after EVERY producer has delivered at
    least one chunk (spawn/startup cost excluded — both transports pay
    identical numpy-only child imports).  ``traj`` switches producers to
    trajectory-shaped chunks (n-step overlap + compressible content);
    ``wire`` enables the tcp wire-efficiency layers (codec/coalesce/
    dedup spec fields) and adds wire-vs-logical byte accounting."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    stop_evt = ctx.Event()
    mod = load_shm_ring()
    rings: List = []
    queues: List = []
    net_tr = None
    if transport == "shm_ring":
        rings = [mod.ShmRing(ring_bytes) for _ in range(workers)]
        procs = _spawn_all(ctx, _ring_producer, [
            (r.name, ring_bytes, w, rows, obs_shape, stop_evt, 10, traj)
            for w, r in enumerate(rings)
        ])
    elif transport == "mp_queue":
        queues = [ctx.Queue(maxsize=8) for _ in range(workers)]
        procs = _spawn_all(ctx, _queue_producer, [
            (q, w, rows, obs_shape, stop_evt) for w, q in enumerate(queues)
        ])
    elif transport == "tcp_loopback":
        net_mod = load_net()
        # Per-connection drain bound: the pool's transport_budget
        # arithmetic (sweep budget / fleet width) at the default budget.
        net_tr = net_mod.NetTransport(
            drain_budget_per_conn=max(64 << 10, (64 << 20) // workers),
            codec=(wire or {}).get("codec", "off"),
        )
        rings = [net_tr.make_channel(w, 0) for w in range(workers)]
        procs = _spawn_all(ctx, _net_producer, [
            ("127.0.0.1", net_tr.port, net_tr.token, w, rows, obs_shape,
             stop_evt, 10, traj, wire)
            for w in range(workers)
        ])
    else:
        raise ValueError(f"unknown transport {transport}")

    rr = [0]  # rotating scan start: a first-match scan from index 0 would
    # never poll later channels while channel 0 has data (with N producers
    # refilling faster than one consumer drains, that is ALWAYS) — the
    # ready phase would livelock waiting for every producer's first chunk.

    def consume_once() -> Optional[tuple]:
        """(wid, nbytes, rows) of one chunk, or None if nothing ready."""
        if net_tr is not None:
            net_tr.pump()  # accept/handshake on the consume cadence
        for i in range(workers):
            w = (rr[0] + i) % workers
            if transport in ("shm_ring", "tcp_loopback"):
                rec = rings[w].read_next()
                if rec is None:
                    continue
                rr[0] = (w + 1) % workers
                return (w, len(rec), rows)
            try:
                msg = queues[w].get_nowait()
            except queue_mod.Empty:
                continue
            rr[0] = (w + 1) % workers
            # Production-shaped cost: touch the arrays the way the pool
            # decode does (pickle already materialized them).
            _, wid, _, prio, tdict, n = msg
            return (wid, prio.nbytes + sum(v.nbytes
                                           for v in tdict.values()), n)
        return None

    try:
        seen = set()
        deadline = time.monotonic() + ready_timeout
        while len(seen) < workers:
            got = consume_once()
            if got is not None:
                seen.add(got[0])
            elif time.monotonic() > deadline:
                raise TimeoutError(
                    f"{transport}: only {len(seen)}/{workers} producers "
                    "delivered within the ready timeout"
                )
            else:
                time.sleep(0.0005)
        t0 = time.monotonic()
        chunks = rows_n = nbytes = 0
        wire0 = net_tr.stats() if net_tr is not None else None
        while time.monotonic() - t0 < seconds:
            got = consume_once()
            if got is None:
                time.sleep(0.0002)
                continue
            chunks += 1
            nbytes += got[1]
            rows_n += got[2]
        elapsed = time.monotonic() - t0
        wire1 = net_tr.stats() if net_tr is not None else None
    finally:
        stop_evt.set()
        for q in queues:  # unblock producers stuck in a full put
            try:
                while True:
                    q.get_nowait()
            except Exception:  # noqa: BLE001 — teardown drain
                pass
        for p in procs:
            p.join(timeout=10.0)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
        for q in queues:
            q.close()
        for r in rings:
            r.close()
            r.unlink()
        if net_tr is not None:
            net_tr.close()
    out = {
        "transport": transport,
        "workers": workers,
        "transitions_per_sec": round(rows_n / elapsed, 1),
        "chunks_per_sec": round(chunks / elapsed, 1),
        "mb_per_sec": round(nbytes / elapsed / 1e6, 2),
        "chunk_transitions": rows,
        "window_s": round(elapsed, 2),
    }
    if wire0 is not None and wire1 is not None and rows_n:
        # Wire-vs-logical byte economics over the timed window (the
        # in-flight skew at the window edges is one coalesced frame per
        # producer — noise at multi-second windows).
        wire_b = wire1["bytes_in"] - wire0["bytes_in"]
        logical_b = wire1["logical_bytes_in"] - wire0["logical_bytes_in"]
        out["wire"] = {
            "codec": (wire or {}).get("codec", "off"),
            "coalesce_bytes": (wire or {}).get("coalesce", 0),
            "dedup": bool((wire or {}).get("dedup", False)),
            "wire_bytes_per_transition": round(wire_b / rows_n, 1),
            "logical_bytes_per_transition": round(logical_b / rows_n, 1),
            "wire_over_logical": (
                round(wire_b / logical_b, 4) if logical_b else None
            ),
            "records_per_frame": wire1["records_per_frame"],
            "codec_decode_ms": round(
                wire1["codec_ms"] - wire0["codec_ms"], 1
            ),
        }
    return out


def run_transport_bench(workers_list: Sequence[int] = (4, 16, 64),
                        seconds: float = 3.0, rows: int = 64,
                        obs_shape=(84, 84, 1),
                        ring_bytes: int = 4 << 20) -> dict:
    points = []
    for w in workers_list:
        mpq = run_transport_point("mp_queue", w, seconds, rows, obs_shape)
        shm = run_transport_point("shm_ring", w, seconds, rows, obs_shape,
                                  ring_bytes=ring_bytes)
        base = max(mpq["transitions_per_sec"], 1e-9)
        points.append({
            "workers": w,
            "mp_queue": mpq,
            "shm_ring": shm,
            "speedup": round(shm["transitions_per_sec"] / base, 2),
        })
    return {
        "points": points,
        "chunk_transitions": rows,
        "obs_shape": list(obs_shape),
        "note": (
            "N producer processes -> 1 consumer, per-worker channels both "
            "ways; timed window starts after every producer's first chunk "
            "(startup excluded); host-only (no jax in any process)"
        ),
    }


def run_net_bench(workers_list: Sequence[int] = (4, 16, 64),
                 seconds: float = 3.0, rows: int = 64,
                 obs_shape=(84, 84, 1), ring_bytes: int = 4 << 20,
                 coalesce_bytes: int = 2 << 20) -> dict:
    """``xp_net``: shm ring vs TCP-loopback vs TCP with the
    wire-efficiency layers (coalesce + in-window frame dedup + zlib), at
    each fleet width — what leaving /dev/shm costs, and what the byte
    economy buys back.  ALL legs feed trajectory-shaped chunks (n-step
    frame overlap + Atari-like compressible content — matched settings),
    so the shm/tcp comparison is content-identical and the wire legs see
    the redundancy real actors emit."""
    points = []
    for w in workers_list:
        shm = run_transport_point("shm_ring", w, seconds, rows, obs_shape,
                                  ring_bytes=ring_bytes, traj=True)
        tcp = run_transport_point("tcp_loopback", w, seconds, rows,
                                  obs_shape, ring_bytes=ring_bytes,
                                  traj=True)
        ded = run_transport_point(
            "tcp_loopback", w, seconds, rows, obs_shape,
            ring_bytes=ring_bytes, traj=True,
            wire={"codec": "off", "coalesce": coalesce_bytes,
                  "dedup": True},
        )
        eff = run_transport_point(
            "tcp_loopback", w, seconds, rows, obs_shape,
            ring_bytes=ring_bytes, traj=True,
            wire={"codec": "zlib", "coalesce": coalesce_bytes,
                  "dedup": True},
        )
        base = max(tcp["transitions_per_sec"], 1e-9)
        base_ded = max(ded["transitions_per_sec"], 1e-9)
        base_eff = max(eff["transitions_per_sec"], 1e-9)
        plain_bpt = tcp.get("wire", {}).get("wire_bytes_per_transition")
        ded_bpt = ded.get("wire", {}).get("wire_bytes_per_transition")
        eff_bpt = eff.get("wire", {}).get("wire_bytes_per_transition")
        points.append({
            "workers": w,
            "shm_ring": shm,
            "tcp_loopback": tcp,
            "tcp_dedup": ded,
            "tcp_wire_eff": eff,
            "shm_over_tcp": round(shm["transitions_per_sec"] / base, 2),
            "shm_over_tcp_dedup": round(
                shm["transitions_per_sec"] / base_ded, 2
            ),
            "shm_over_tcp_wire_eff": round(
                shm["transitions_per_sec"] / base_eff, 2
            ),
            "wire_bytes_reduction_x_dedup": (
                round(plain_bpt / ded_bpt, 2)
                if plain_bpt and ded_bpt else None
            ),
            "wire_bytes_reduction_x": (
                round(plain_bpt / eff_bpt, 2)
                if plain_bpt and eff_bpt else None
            ),
        })
    return {
        "points": points,
        "chunk_transitions": rows,
        "obs_shape": list(obs_shape),
        "wire_eff": {"codec": "zlib", "coalesce_bytes": coalesce_bytes,
                     "dedup": True},
        "note": (
            "N producer processes -> 1 consumer; identical CRC-framed "
            "APXT records on every leg (shm ring vs runtime/net.py TCP "
            "loopback: plain, coalesce+dedup, coalesce+dedup+zlib); "
            "trajectory-shaped chunks (obs[i+n]==next_obs[i], static "
            "background + moving sprite) on every leg — matched "
            "settings; timed window starts after every producer's first "
            "chunk; host-only (no jax in any process).  NB loopback on "
            "a 1-core driver VM prices CPU, not the wire: the codec leg "
            "trades CPU it doesn't have for bytes that are free there — "
            "a real cross-host link inverts that trade (net_codec=auto "
            "is the arbiter)"
        ),
    }


def run_sigkill_barrage(workers: int = 4, rounds: int = 2, rows: int = 64,
                        obs_shape=(84, 84, 1),
                        ring_bytes: int = 1 << 20) -> dict:
    """Kill ring producers at random moments mid-stream, then salvage.

    Asserts the transport's core safety property, per ring per round:
    every chunk the producer committed is drained intact and in order
    (``lost_committed == 0``), and a kill that landed mid-record is
    detected as a torn tail rather than corrupting the stream.
    """
    import multiprocessing as mp

    mod = load_shm_ring()
    ctx = mp.get_context("spawn")
    rng = np.random.default_rng(0)
    killed = committed_total = consumed_total = lost = torn = 0
    seq_errors = 0
    for _ in range(rounds):
        stop_evt = ctx.Event()
        rings = [mod.ShmRing(ring_bytes) for _ in range(workers)]
        procs = _spawn_all(ctx, _ring_producer, [
            (r.name, ring_bytes, w, rows, obs_shape, stop_evt)
            for w, r in enumerate(rings)
        ])
        try:
            consumed = [0] * workers
            next_seq = [0] * workers

            def drain_all():
                nonlocal seq_errors
                for w, r in enumerate(rings):
                    while True:
                        rec = r.read_next()
                        if rec is None:
                            break
                        # version field carries the producer's chunk seq —
                        # must arrive contiguous from 0.
                        _, version, *_ = mod.decode_chunk(rec)
                        if version != next_seq[w]:
                            seq_errors += 1
                        next_seq[w] += 1
                        consumed[w] += 1

            # Let every producer commit at least one record (kills during
            # the child's numpy-import window prove nothing).
            deadline = time.monotonic() + 180.0
            while any(r.committed == 0 for r in rings):
                drain_all()
                if time.monotonic() > deadline:
                    raise TimeoutError("barrage producers never delivered")
                time.sleep(0.001)
            # Staggered random kills while the consumer keeps draining, so
            # writers are actively copying (not parked in backpressure)
            # when the SIGKILL lands.
            order = rng.permutation(workers)
            for w in order:
                t_kill = time.monotonic() + float(rng.uniform(0.01, 0.15))
                while time.monotonic() < t_kill:
                    drain_all()
                os.kill(procs[w].pid, signal.SIGKILL)
                killed += 1
            for p in procs:
                p.join(timeout=10.0)
            drain_all()  # full salvage of the dead incarnations
            for w, r in enumerate(rings):
                committed_total += r.committed
                consumed_total += consumed[w]
                lost += max(0, r.committed - consumed[w])
                if r.torn_tail():
                    torn += 1
        finally:
            # No stop_evt.set() here: a producer SIGKILLed inside
            # stop_evt.is_set() died holding the event's lock, and set()
            # would then block forever.  Whoever outlived the barrage (an
            # exception path) is terminated instead.
            for p in procs:
                if p.is_alive():
                    p.terminate()
                    p.join(timeout=5.0)
            for r in rings:
                r.close()
                r.unlink()
    return {
        "producers_killed": killed,
        "committed_chunks": committed_total,
        "salvaged_chunks": consumed_total,
        "lost_committed_chunks": lost,
        "seq_errors": seq_errors,
        "torn_tails_detected": torn,
        "note": (
            "SIGKILL at random moments mid-stream; salvage must recover "
            "every fully-committed chunk in order (consumed may exceed the "
            "committed counter by <=1/ring: a kill can land between the "
            "record's commit word and the counter update)"
        ),
    }


if __name__ == "__main__":
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workers", default="4,16,64")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--obs", default="84x84x1")
    ap.add_argument("--skip-barrage", action="store_true")
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI gate (tools/verify_t1.sh): one tiny point and a one-round "
        "barrage, seconds not minutes; exits non-zero on a lost committed "
        "chunk, so an import-time regression in the transport can't reach "
        "the driver unseen",
    )
    args = ap.parse_args()
    if args.smoke:
        small = dict(rows=16, obs_shape=(16, 16, 1))
        out = run_transport_bench([2], seconds=0.5, **small)
        bar = out["sigkill_barrage"] = run_sigkill_barrage(
            workers=2, rounds=1, **small)
        print(json.dumps({"xp_transport_smoke": out}))
        lost = bar["lost_committed_chunks"] or bar["seq_errors"]
        sys.exit(f"xp_transport smoke: lost or misordered chunks: {bar}"
                 if lost else 0)
    obs = tuple(int(x) for x in args.obs.split("x"))
    out = {
        "bench": run_transport_bench(
            [int(w) for w in args.workers.split(",")],
            seconds=args.seconds, rows=args.rows, obs_shape=obs,
        ),
    }
    if not args.skip_barrage:
        out["sigkill_barrage"] = run_sigkill_barrage(
            rows=args.rows, obs_shape=obs,
        )
    print(json.dumps(out))
