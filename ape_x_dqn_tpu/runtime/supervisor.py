"""Fleet supervision: the policy layer over every recovery signal.

Ape-X's premise — hundreds of decoupled actors feeding one learner — only
holds at scale if any component can die without taking the run down.  The
repo has the *mechanisms* (SIGKILL-safe shm rings with salvage, the
incremental checkpoint chain with generation fallback, per-component
heartbeats on /healthz); this module is the *policy* tier that consumes
them, one typed policy per failure class:

  * :class:`RespawnPolicy` — worker deaths respawn with exponential
    backoff + jitter under a crash-loop budget: a worker that keeps dying
    inside the sliding window is QUARANTINED (the fleet shrinks
    gracefully; no hot-loop of spawn→crash→spawn) instead of either
    spinning the pool or — the old ``max_restarts`` behavior — declaring
    the whole run failed.  ``ProcessActorPool.supervise()`` consults it
    for every death.
  * :class:`LearnerWatchdog` — no observable learner progress (its
    step count) for ``stall_deadline_s`` first DEGRADES (an event and
    the caller's hook, where it gave one); still nothing
    ``wedge_deadline_s`` later and the run is declared WEDGED — a
    structured event plus a failing /healthz component, the operator
    signal, never a silent hang.
  * **Serving staleness** — :class:`ServingStalenessPolicy` flips a
    PolicyServer into degraded mode (submissions shed with the typed
    ``ServerOverloaded``; /healthz 503) when its params age past
    ``serving.param_stale_s``, and back when a fresh snapshot lands.
  * **Checkpoint fallback accounting** — degraded restores recorded by
    ``utils.checkpoint_inc`` (generation walk-backs on a corrupt chunk)
    are drained into the ``supervisor/fallback_restores`` counter so the
    fleet's recovery history is one scrape, not a log grep.

Everything lands on the obs registry: ``supervisor/respawns`` /
``quarantines`` / ``degradations`` / ``fallback_restores`` counters plus
a ``supervisor`` provider section (per-worker backoff state, quarantine
list, watchdog phase) on /varz, /metrics and the JSONL emit —
docs/METRICS.md rows, pinned by tests.

Deterministic where it matters: the jitter rng is seeded, and every
policy method takes an explicit ``now`` so tests drive time instead of
sleeping through backoff windows.
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

# Respawn decisions (RespawnPolicy.decide) — a tiny closed vocabulary the
# pool switches on.
RESPAWN = "respawn"
WAIT = "wait"
QUARANTINE = "quarantine"


class RespawnPolicy:
    """Per-worker respawn discipline: exponential backoff + jitter inside
    a crash-loop budget.

    ``on_death(wid)`` records a death; ``decide(wid)`` answers what the
    pool should do *right now*: ``RESPAWN`` (the backoff has elapsed),
    ``WAIT`` (still backing off — ask again next sweep), or
    ``QUARANTINE`` (more than ``budget`` deaths inside ``window_s``: the
    worker is written off and the fleet shrinks).  Backoff doubles per
    death currently inside the window and carries multiplicative jitter
    so a correlated fleet-wide kill does not respawn in lockstep.
    """

    def __init__(self, base_s: float = 0.5, max_s: float = 30.0,
                 jitter: float = 0.25, window_s: float = 120.0,
                 budget: int = 5, seed: int = 0):
        self.base_s = float(base_s)
        self.max_s = float(max_s)
        self.jitter = float(jitter)
        self.window_s = float(window_s)
        self.budget = int(budget)
        self._rng = random.Random(seed ^ 0x5E5)
        self._deaths: Dict[int, deque] = {}
        self._next_ok: Dict[int, float] = {}
        self.quarantined: set = set()

    def _window(self, wid: int, now: float) -> deque:
        d = self._deaths.setdefault(wid, deque())
        while d and now - d[0] > self.window_s:
            d.popleft()
        return d

    def on_death(self, wid: int, now: Optional[float] = None) -> str:
        """Record one death; returns the immediate verdict (``QUARANTINE``
        when this death blows the budget, else ``WAIT`` with the backoff
        armed)."""
        now = time.monotonic() if now is None else now
        d = self._window(wid, now)
        d.append(now)
        if len(d) > self.budget:
            self.quarantined.add(wid)
            return QUARANTINE
        backoff = min(self.base_s * (2.0 ** (len(d) - 1)), self.max_s)
        backoff *= 1.0 + self.jitter * (2.0 * self._rng.random() - 1.0)
        self._next_ok[wid] = now + backoff
        return WAIT

    def decide(self, wid: int, now: Optional[float] = None) -> str:
        now = time.monotonic() if now is None else now
        if wid in self.quarantined:
            return QUARANTINE
        if now < self._next_ok.get(wid, 0.0):
            return WAIT
        return RESPAWN

    def backoff_remaining(self, wid: int, now: Optional[float] = None) -> float:
        now = time.monotonic() if now is None else now
        return max(0.0, self._next_ok.get(wid, 0.0) - now)

    def state(self, now: Optional[float] = None) -> dict:
        now = time.monotonic() if now is None else now
        return {
            str(wid): {
                "deaths_in_window": len(self._window(wid, now)),
                "backoff_remaining_s": round(
                    self.backoff_remaining(wid, now), 3
                ),
                "quarantined": wid in self.quarantined,
            }
            for wid in sorted(set(self._deaths) | self.quarantined)
        }


class LearnerWatchdog:
    """Progress watchdog with a degrade-before-wedge ladder.

    ``progress_fn`` returns any hashable progress token (the pipeline uses
    its learner step); a token unchanged for
    ``stall_deadline_s`` triggers ``degrade_fn`` ONCE (phase ``degraded``),
    and a token still unchanged ``wedge_deadline_s`` after the degrade
    declares the run ``wedged``.  Any progress resets the ladder to
    ``ok`` — a degrade that unstuck the run self-clears.
    """

    def __init__(self, progress_fn: Callable[[], object],
                 degrade_fn: Optional[Callable[[], None]] = None,
                 stall_deadline_s: float = 120.0,
                 wedge_deadline_s: float = 120.0,
                 on_event: Optional[Callable[..., None]] = None):
        self._progress_fn = progress_fn
        self._degrade_fn = degrade_fn
        self.stall_deadline_s = float(stall_deadline_s)
        self.wedge_deadline_s = float(wedge_deadline_s)
        self._on_event = on_event
        self.phase = "ok"            # ok -> degraded -> wedged
        self.degradations = 0
        self._last_token = None
        self._last_progress: Optional[float] = None

    def check(self, now: Optional[float] = None) -> str:
        now = time.monotonic() if now is None else now
        try:
            token = self._progress_fn()
        except Exception:  # noqa: BLE001 — an unreadable learner is stalled
            token = self._last_token
        if self._last_progress is None or token != self._last_token:
            self._last_token = token
            self._last_progress = now
            if self.phase != "ok" and token is not None:
                self._event("watchdog_recovered", phase_was=self.phase)
                self.phase = "ok"
            return self.phase
        stalled_s = now - self._last_progress
        if self.phase == "ok" and stalled_s > self.stall_deadline_s:
            self.phase = "degraded"
            self.degradations += 1
            self._event("pipeline_degraded", stalled_s=round(stalled_s, 1))
            if self._degrade_fn is not None:
                try:
                    self._degrade_fn()
                except Exception:  # noqa: BLE001 — degrade is best-effort
                    pass
            # The degrade restarts the wedge clock: give strict mode a
            # full deadline to show progress before declaring defeat.
            self._last_progress = now
        elif self.phase == "degraded" and stalled_s > self.wedge_deadline_s:
            self.phase = "wedged"
            self._event("run_wedged", stalled_s=round(stalled_s, 1))
        return self.phase

    def age_s(self) -> float:
        """Health age fn: 0 while ok/degraded-but-progressing, +inf once
        wedged (the /healthz 503 signal)."""
        return float("inf") if self.phase == "wedged" else 0.0

    def _event(self, kind: str, **fields) -> None:
        if self._on_event is not None:
            try:
                self._on_event(kind, **fields)
            except Exception:  # noqa: BLE001 — observer callback must never break supervision
                pass


class ServingStalenessPolicy:
    """Degrade a PolicyServer whose param source went quiet.

    ``check()`` compares the server's param age against ``stale_after_s``
    and toggles the server's degraded flag (submissions shed with the
    typed ``ServerOverloaded``); recovery is automatic when a fresh
    snapshot is adopted.  ``age_s`` doubles as the /healthz component
    (register with ``stale_after_s`` as its bound).
    """

    def __init__(self, server, stale_after_s: float,
                 on_event: Optional[Callable[..., None]] = None):
        self._server = server
        self.stale_after_s = float(stale_after_s)
        self._on_event = on_event
        self.transitions = 0

    def age_s(self) -> float:
        return self._server.param_age_s

    def check(self, now: Optional[float] = None) -> bool:
        """Returns the (possibly toggled) degraded state."""
        stale = self.age_s() > self.stale_after_s
        if stale != self._server.degraded:
            self._server.degraded = stale
            self.transitions += 1
            if self._on_event is not None:
                try:
                    self._on_event(
                        "serving_degraded" if stale else "serving_recovered",
                        param_age_s=round(self.age_s(), 3),
                        stale_after_s=self.stale_after_s,
                    )
                except Exception:  # noqa: BLE001 — staleness events are telemetry; shedding still happens
                    pass
        return stale


class FleetSupervisor:
    """One supervisor per run: owns the policies, the counters, and the
    background thread that ticks the watchdogs.

    Wiring (AsyncPipeline does all of this):

      * construction registers the four ``supervisor/*`` counters and the
        ``supervisor`` provider on the registry, and drains any
        ``degraded_restore`` events a pre-supervisor restore already
        recorded (checkpoint_inc.consume_fallback_events);
      * ``attach_pool(pool)`` installs the respawn policy — the pool's
        ``supervise()`` calls back into it per death;
      * ``attach_learner(progress_fn, degrade_fn)`` arms the watchdog
        (and its /healthz component, when a Health is given);
      * ``attach_serving(server)`` arms staleness shedding;
      * ``start()``/``close()`` run the ``poll_s`` tick thread.
    """

    def __init__(self, cfg, registry=None, health=None,
                 emit: Optional[Callable[..., None]] = None,
                 seed: int = 0):
        self.cfg = cfg
        self._health = health
        self._emit = emit
        self.events: List[dict] = []
        reg = registry
        if reg is None:
            from ape_x_dqn_tpu.obs.registry import MetricsRegistry

            reg = MetricsRegistry()
        self.registry = reg
        self.respawns = reg.counter(
            "supervisor/respawns", help="worker respawns ordered"
        )
        self.quarantines = reg.counter(
            "supervisor/quarantines", help="workers quarantined (crash loop)"
        )
        self.degradations = reg.counter(
            "supervisor/degradations",
            help="degraded-mode transitions (pipeline strict, serving shed)",
        )
        self.fallback_restores = reg.counter(
            "supervisor/fallback_restores",
            help="checkpoint restores that walked back a corrupt chain",
        )
        reg.register_provider("supervisor", self.state)
        self.respawn_policy = RespawnPolicy(
            base_s=cfg.respawn_backoff_base_s,
            max_s=cfg.respawn_backoff_max_s,
            jitter=cfg.respawn_jitter,
            window_s=cfg.crash_loop_window_s,
            budget=cfg.crash_loop_budget,
            seed=seed,
        )
        self.watchdog: Optional[LearnerWatchdog] = None
        self.serving_policies: List[ServingStalenessPolicy] = []
        self._pool = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Restores that degraded before this supervisor existed (the
        # build_components replay leg) still count.
        from ape_x_dqn_tpu.utils.checkpoint_inc import consume_fallback_events

        for ev in consume_fallback_events():
            self.note_fallback_restore(ev)

    # -- event plumbing ----------------------------------------------------

    def _event(self, kind: str, **fields) -> None:
        rec = {"kind": kind, **fields}
        self.events.append(rec)
        if len(self.events) > 1024:
            del self.events[:256]
        if self._emit is not None:
            try:
                self._emit(kind, **fields)
            except Exception:  # noqa: BLE001 — telemetry must not supervise
                pass

    # -- worker respawn (pool callback surface) ----------------------------

    def attach_pool(self, pool) -> "FleetSupervisor":
        self._pool = pool
        pool.respawn_policy = self
        return self

    def on_worker_death(self, wid: int, error: str,
                        now: Optional[float] = None) -> str:
        verdict = self.respawn_policy.on_death(wid, now)
        if verdict == QUARANTINE:
            self.quarantines.inc()
            self._event("worker_quarantined", worker=wid, error=error,
                        deaths_in_window=len(
                            self.respawn_policy._deaths.get(wid, ())
                        ))
        else:
            self._event("worker_death", worker=wid, error=error,
                        backoff_s=round(
                            self.respawn_policy.backoff_remaining(wid, now), 3
                        ))
        return verdict

    def decide_respawn(self, wid: int, now: Optional[float] = None) -> str:
        verdict = self.respawn_policy.decide(wid, now)
        if verdict == RESPAWN:
            self.respawns.inc()
            self._event("worker_respawn", worker=wid)
        return verdict

    # -- learner watchdog --------------------------------------------------

    def attach_learner(self, progress_fn: Callable[[], object],
                       degrade_fn: Optional[Callable[[], None]] = None
                       ) -> "FleetSupervisor":
        def _degrade():
            self.degradations.inc()
            if degrade_fn is not None:
                degrade_fn()

        self.watchdog = LearnerWatchdog(
            progress_fn, _degrade,
            stall_deadline_s=self.cfg.stall_deadline_s,
            wedge_deadline_s=self.cfg.wedge_deadline_s,
            on_event=self._event,
        )
        if self._health is not None:
            self._health.register("supervisor", self.watchdog.age_s)
        return self

    # -- serving staleness -------------------------------------------------

    def attach_serving(self, server, stale_after_s: float
                       ) -> ServingStalenessPolicy:
        def _on_event(kind, **fields):
            if kind == "serving_degraded":
                self.degradations.inc()
            self._event(kind, **fields)

        policy = ServingStalenessPolicy(
            server, stale_after_s, on_event=_on_event
        )
        self.serving_policies.append(policy)
        if self._health is not None:
            self._health.register(
                "serving_params", policy.age_s, stale_after_s=stale_after_s
            )
        return policy

    # -- checkpoint fallback -----------------------------------------------

    def note_fallback_restore(self, event: dict) -> None:
        self.fallback_restores.inc()
        self._event("degraded_restore", **{
            k: v for k, v in event.items() if k != "event"
        })

    # -- the tick thread ---------------------------------------------------

    def tick(self, now: Optional[float] = None) -> None:
        if self.watchdog is not None:
            self.watchdog.check(now)
        for policy in self.serving_policies:
            policy.check(now)

    def start(self) -> "FleetSupervisor":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="fleet-supervisor", daemon=True
            )
            self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(float(self.cfg.poll_s)):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — the supervisor outlives all
                pass

    # -- the /varz section -------------------------------------------------

    def state(self) -> dict:
        out: dict = {
            "workers": self.respawn_policy.state(),
            "quarantined": sorted(self.respawn_policy.quarantined),
            "watchdog": (
                self.watchdog.phase if self.watchdog is not None else None
            ),
            "serving_degraded": any(
                p._server.degraded for p in self.serving_policies
            ),
            "recent_events": self.events[-8:],
        }
        return out
