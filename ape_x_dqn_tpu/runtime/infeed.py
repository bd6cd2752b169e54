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
from typing import Callable, Optional

import jax


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
