"""Device infeed: prefetch replay samples onto the TPU behind the train step.

The reference's learner pays a full cross-process RPC + pickle of a frame
batch for every update, synchronously, before it can compute (reference
learner.py:68, §3.3 "where the time actually goes").  The TPU equivalent of
that stall is the device idling while the host samples + transfers.  This
module hides it: a feeder thread samples from the replay and ``device_put``s
the batch into a small bounded queue while the previous step runs — the
host↔device overlap that SURVEY §7 ranks as hard part #2.

Queue depth 2 is classic double buffering: one batch in flight on device,
one staged.  Deeper queues only add priority-staleness (batches sampled
long before they are learned from see older priorities), so depth stays a
knob with a small default.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, List, Optional

import jax
import numpy as np


class PrefetchQueue:
    """Feeder thread: ``sample_fn() -> host batch`` → device → bounded queue.

    Args:
      sample_fn: returns the next host batch (thread-safe; typically closes
        over replay.sample with the β schedule).
      place_fn: host batch → device batch (``jax.device_put`` or the mesh
        ``place_batch``); defaults to plain device_put.
      depth: max staged batches (2 = double buffering).
    """

    def __init__(
        self,
        sample_fn: Callable[[], object],
        place_fn: Optional[Callable[[object], object]] = None,
        depth: int = 2,
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._sample_fn = sample_fn
        self._place_fn = place_fn or jax.device_put
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name="infeed-prefetch", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                batch = self._place_fn(self._sample_fn())
                # Bounded put with timeout so stop() is honored promptly.
                while not self._stop.is_set():
                    try:
                        self._q.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # surface in get()
            self._error = e

    def get(self, timeout: float = 30.0):
        """Next staged device batch; re-raises feeder errors.

        ``timeout`` is a wall-clock deadline from CALL ENTRY: the previous
        spelling only started counting after the first ``queue.Empty`` and
        waited a flat ``min(0.2, timeout)`` per retry regardless of the
        remaining budget, so a ``get(10.0)`` could block ~10.2 s and a
        sub-200 ms timeout overshot by up to a whole retry period.  Each
        wait is still capped at 0.2 s so feeder errors surface promptly.
        """
        deadline = time.monotonic() + timeout
        while True:
            if self._error is not None:
                raise RuntimeError("infeed feeder failed") from self._error
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError("infeed queue starved") from None
            try:
                return self._q.get(timeout=min(0.2, remaining))
            except queue.Empty:
                continue

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


class DispatchPipeline:
    """Overlapped fused-dispatch window: chain learner dispatches with zero
    intervening host syncs, draining outputs one dispatch behind.

    A blocking read between dispatches empties the device queue — the
    device idles for the host round trip.  This window keeps up to
    ``depth`` fused calls in flight:

      * ``dispatch(fn, steps)`` runs one fused call, starts an **async**
        device→host copy of its probe leaf (the tiny array whose host read
        forces the whole call), and registers it.
      * ``drain_ready()`` retires calls whose probe has **already landed**
        (``jax.Array.is_ready``) — a free read, not a host sync: the data
        crossed while the device kept executing queued work.
      * when ``depth`` is reached, the window waits for the oldest call by
        POLLING its readiness (short sleeps) instead of issuing a blocking
        device read: the device still holds ``depth-1`` queued programs,
        so the wait idles the host, not the device, and the retire-read
        touches only landed data — no synchronous round trip.  Only if the poll deadline expires does the host
        hard-block, and only that (plus cadence syncs below) is counted on
        the ``learner/host_syncs`` counter.  At ``depth=1`` the wait IS a
        hard block (strict semantics: the host synchronously reads each
        dispatch's outputs — the per-call sync the pipeline exists to
        amortize), so strict runs count one sync per call.
      * ``sync()`` is the explicit full drain (the ``learner.sync_every``
        cadence, emit/exit boundaries): blocks until every in-flight call
        has completed, counted as ONE sync event however many calls it
        retires (one burst, one post-sync charge).

    Overlap accounting: the device sat idle between dispatches iff the
    NEWEST in-flight call finished before the next dispatch was enqueued.
    ``dispatch`` checks exactly that — if the newest probe is ready the gap
    since the device was last observed busy is recorded on the
    ``learner/overlap_gap_ms`` histogram, else 0 ms (the device was still
    chewing when new work arrived: ingest fully hidden).  The p50 of that
    histogram ≈ 0 is the bench's "ingest wall-clock hidden" criterion.

    Not thread-safe: one learner thread owns it, like the fused learner.
    ``depth=1`` degenerates to strict dispatch-then-force (every call
    blocks, every block counts) — the equivalence oracle.
    """

    def __init__(
        self,
        depth: int,
        probe_fn: Callable[[object], object],
        on_retire: Optional[Callable[[object, int], None]] = None,
        sync_counter=None,
        gap_hist_ms=None,
        poll_s: float = 5e-4,
        poll_deadline_s: float = 120.0,
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.depth = int(depth)
        self._probe_fn = probe_fn
        self._on_retire = on_retire
        self._sync_counter = sync_counter
        self._gap_hist = gap_hist_ms
        self._poll_s = float(poll_s)
        self._poll_deadline_s = float(poll_deadline_s)
        self._inflight: List[tuple] = []  # (metrics, probe, steps)
        self._last_busy = time.monotonic()
        self._dispatched = 0
        self.host_syncs = 0       # blocking drains (mirrors the obs counter)
        self.gaps_observed = 0
        self.steps_inflight = 0

    def __len__(self) -> int:
        return len(self._inflight)

    # -- internals --------------------------------------------------------

    @staticmethod
    def _ready(probe) -> bool:
        is_ready = getattr(probe, "is_ready", None)
        if is_ready is None:
            return True  # host value (numpy): nothing to wait for
        return bool(is_ready())

    def _retire(self, entry) -> None:
        metrics, probe, steps = entry
        # The probe read forces the call; by retire time it is usually
        # already host-side from the async copy started at dispatch.
        np.asarray(probe)
        # Observation point for idle accounting: the device finished this
        # call at or before now, so a later empty-window gap measured from
        # here is a LOWER bound on the true idle time (conservative).
        self._last_busy = time.monotonic()
        self.steps_inflight -= steps
        if self._on_retire is not None:
            self._on_retire(metrics, steps)

    def _count_sync(self) -> None:
        self.host_syncs += 1
        if self._sync_counter is not None:
            self._sync_counter.inc()

    def _record_gap(self, gap_s: float) -> None:
        self.gaps_observed += 1
        if self._gap_hist is not None:
            self._gap_hist.observe(gap_s * 1e3)

    # -- the dispatch path ------------------------------------------------

    def dispatch(self, fn: Callable[[], object], steps: int):
        """Run one fused call via ``fn`` and register its output.

        Measures the overlap gap first (was the device idle when this work
        arrived?), dispatches, starts the async probe copy, then applies
        flow control: retire everything already complete, and if the
        window is still at ``depth``, block on the oldest (a host sync iff
        it had not finished).  Returns ``fn()``'s result unmodified.
        """
        now = time.monotonic()
        if self._inflight:
            newest_probe = self._inflight[-1][1]
            if self._ready(newest_probe):
                # Device drained its queue before new work arrived: idle
                # since some point after we last saw it busy — report that
                # (bounded) window.
                self._record_gap(max(0.0, now - self._last_busy))
            else:
                self._record_gap(0.0)
                self._last_busy = now
        elif self._dispatched:
            # Empty window: nothing queued, so the device has been idle at
            # least since the last retire observation.
            self._record_gap(max(0.0, now - self._last_busy))
        metrics = fn()
        self._dispatched += 1
        self._last_busy = time.monotonic()  # new work enqueued
        probe = self._probe_fn(metrics)
        start_copy = getattr(probe, "copy_to_host_async", None)
        if start_copy is not None:
            start_copy()
        self._inflight.append((metrics, probe, int(steps)))
        self.steps_inflight += int(steps)
        self.drain_ready()
        if len(self._inflight) >= self.depth:
            # Window full: the oldest must come home before we run ahead.
            entry = self._inflight.pop(0)
            if self.depth == 1:
                # Strict force-every-call policy: a synchronous read of
                # the dispatch just issued — the per-call host sync the
                # pipeline amortizes away at depth > 1.
                if not self._ready(entry[1]):
                    self._count_sync()
            elif not self._ready(entry[1]):
                # Poll-wait instead of a blocking read: the device still
                # holds depth-1 queued programs (it cannot idle), the host
                # sleeps until the oldest's async copy lands, and the
                # retire-read then touches only host-resident data.  Only
                # a blown deadline degrades to a hard (counted) block.
                deadline = time.monotonic() + self._poll_deadline_s
                while not self._ready(entry[1]):
                    if time.monotonic() > deadline:
                        self._count_sync()
                        break
                    time.sleep(self._poll_s)
            self._retire(entry)
        return metrics

    def degrade(self) -> None:
        """Supervisor action (runtime/supervisor.LearnerWatchdog): drop to
        strict depth 1.  Every subsequent dispatch forces synchronously, so
        a stall can no longer hide inside a deep in-flight window — the
        degraded-but-observable mode the watchdog buys time with before
        declaring the run wedged.  An int store, safe from any thread; the
        learner thread sees it at its next flow-control check."""
        self.depth = 1

    def drain_ready(self) -> int:
        """Retire every in-flight call whose probe already landed — never
        blocks, never counts as a host sync."""
        n = 0
        while self._inflight and self._ready(self._inflight[0][1]):
            self._retire(self._inflight.pop(0))
            n += 1
        return n

    def sync(self) -> int:
        """Full blocking drain (cadence / emit / exit).  One sync event —
        a single burst, however many calls it retires; free if everything
        already landed."""
        if not self._inflight:
            return 0
        if not all(self._ready(e[1]) for e in self._inflight):
            self._count_sync()
        n = 0
        while self._inflight:
            self._retire(self._inflight.pop(0))
            n += 1
        return n
