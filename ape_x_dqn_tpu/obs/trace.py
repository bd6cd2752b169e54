"""On-demand ``jax.profiler`` capture, triggered from /varz?trace=1.

Hit ``/varz?trace=1`` on a LIVE trainer and a background thread traces the
next N learner steps into a TensorBoard logdir, then reduces the xplane
with the program's own reader (``utils/profiling.summarize_trace``:
``jax.profiler.ProfileData``, nothing else): device busy share, seconds
per stage of the fused learner, and the longest device gaps named by the
runtime's ``apex:<stage>`` span beside each.  The record lands in
``<logdir>/summary.json``.

Hitting the endpoint must never kill a run: a profiler that fails to
start or stop (``utils/profiling.trace`` raises) is reported as
``state: "error"`` with the reason, never as a normal outcome.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Callable, Optional


class TraceOnDemand:
    """One in-flight capture at a time; ``trigger()`` returns immediately
    with a status dict (the /varz reply), the capture thread does the
    waiting."""

    def __init__(self, step_fn: Optional[Callable[[], int]] = None,
                 steps: int = 512, out_dir: Optional[str] = None,
                 timeout_s: float = 60.0):
        self._step_fn = step_fn
        self._steps = int(steps)
        self._out_dir = out_dir
        self._timeout_s = float(timeout_s)
        self._lock = threading.Lock()
        self._busy = False
        self.last: dict = {"state": "idle"}

    def trigger(self, steps: Optional[int] = None) -> dict:
        with self._lock:
            if self._busy:
                return {"state": "already-running", **self.last}
            self._busy = True
        n = int(steps) if steps else self._steps
        logdir = self._out_dir or tempfile.mkdtemp(prefix="obs_trace_")
        self.last = {"state": "capturing", "logdir": logdir, "steps": n}
        threading.Thread(
            target=self._capture, args=(logdir, n),
            name="obs-trace-capture", daemon=True,
        ).start()
        return dict(self.last)

    def status(self) -> dict:
        return dict(self.last)

    def _capture(self, logdir: str, n: int) -> None:
        from ape_x_dqn_tpu.utils.profiling import summarize_trace, trace

        rec = {"logdir": logdir, "steps_requested": n}
        try:
            start = self._step_fn() if self._step_fn else 0
            deadline = time.monotonic() + self._timeout_s
            t0 = time.monotonic()
            with trace(logdir):
                if self._step_fn is not None:
                    while (self._step_fn() < start + n
                           and time.monotonic() < deadline):
                        time.sleep(0.05)
                    rec["steps_traced"] = self._step_fn() - start
                else:
                    time.sleep(min(2.0, self._timeout_s))
            rec["wall_s"] = round(time.monotonic() - t0, 3)
            try:
                rec["summary"] = summarize_trace(logdir)
            except Exception as e:  # noqa: BLE001 — the trace is on disk
                rec["summary"] = {"error": f"{type(e).__name__}: {e}"}
            try:
                with open(os.path.join(logdir, "summary.json"),
                          "w") as f:
                    json.dump(rec, f, default=str)
            except OSError:
                pass
            rec["state"] = "done"
        except Exception as e:  # noqa: BLE001 — must never kill the run
            rec["state"] = "error"
            rec["reason"] = f"{type(e).__name__}: {e}"
        finally:
            self.last = rec
            with self._lock:
                self._busy = False
