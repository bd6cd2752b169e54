"""Elastic autopilot: the SLO-driven capacity controller (ROADMAP item 3).

PR 14 built the SENSOR half — the fleet rollup (``obs/fleet.py
FleetAggregator``) and the declarative SLO engine whose burn-rate
windows emit typed ``slo_breach``/``slo_clear`` events.  This package is
the ACTUATION half: one :class:`AutopilotController` (own thread,
``autopilot.*`` knobs, default off) consuming that event stream plus the
rollup and driving CAPACITY, not just recovery:

  * **actor fleet** — grow/retire worker processes through the pool's
    elastic primitives (``ProcessActorPool.grow``/``retire``: fresh wids
    on the SAME global ε-ladder partition, scale-down via clean drain,
    never SIGKILL) and tune the drain budget, to hold
    age-of-experience p95 under its bound and ring occupancy in band;
  * **serving fleet** — grow/retire replicas through
    ``ServingFleet.spawn()`` and the router's proven zero-drop
    drain-from-rotation (``retire``), against the QPS-floor / p99 SLOs;
  * **replay fleet** — grow/retire replay shards through
    ``ReplayServiceFleet.grow()``/``retire()`` (live slot-range
    resharding with a digest-proven handoff), against the per-shard
    add-QPS pressure signal (``obs.fleet_slo_replay_add_qps_high`` up,
    ``autopilot.replay_idle_add_qps_per_shard`` down).

Every decision passes the shared guardrails (min/max bounds,
per-direction cooldowns, a hold window against the opposite direction —
hysteresis ON TOP of the SLO engine's burn windows — and one step at a
time), so a flapping signal can never oscillate capacity.  Every action
emits a typed ``autopilot_action`` event naming its triggering rule;
``autopilot.dry_run`` logs decisions without actuating.

Import-light at module scope (stdlib only): the controller lives in the
trainer process, but tools mount it next to an aggregator on hosts that
never import jax.
"""

from __future__ import annotations

import importlib

_LAZY = {
    "AutopilotController": "ape_x_dqn_tpu.autopilot.controller",
    "Guardrails": "ape_x_dqn_tpu.autopilot.controller",
    "ActorPoolActuator": "ape_x_dqn_tpu.autopilot.actuators",
    "ServingFleetActuator": "ape_x_dqn_tpu.autopilot.actuators",
    "ReplayFleetActuator": "ape_x_dqn_tpu.autopilot.actuators",
}

__all__ = sorted(_LAZY)


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(mod), name)


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
