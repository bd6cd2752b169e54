"""Elastic autopilot (ISSUE 15): controller decision logic on synthetic
SLO streams (no subprocesses), guardrail units, the autopilot schema
pin, and the pool's elastic grow/retire arithmetic — plus one real
process-pool grow/retire e2e (the only test here that spawns anything).
"""

from __future__ import annotations

import os
import time

import pytest

from ape_x_dqn_tpu.autopilot import AutopilotController, Guardrails
from ape_x_dqn_tpu.config import ApexConfig, AutopilotConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_cfg(**kw) -> AutopilotConfig:
    base = dict(
        enabled=True, dry_run=False, poll_s=0.1,
        actor_min_workers=1, serving_min_replicas=1,
        serving_max_replicas=4, cooldown_up_s=5.0, cooldown_down_s=5.0,
        hold_opposite_s=8.0, serving_idle_qps_per_replica=0.0,
        idle_window_s=10.0, drain_tune_max_factor=4.0,
    )
    base.update(kw)
    return AutopilotConfig(**base)


class FakeServing:
    def __init__(self, size=1, busy=False, exhausted=False):
        self._size = size
        self._busy = busy
        self._exhausted = exhausted
        self.calls = []

    def size(self):
        return self._size

    def busy(self):
        return self._busy

    def scale_up(self):
        if self._exhausted:
            return None
        self.calls.append("up")
        self._size += 1
        return {"rid": self._size}

    def scale_down(self):
        if self._exhausted:
            return None
        self.calls.append("down")
        self._size -= 1
        return {"rid": self._size + 1}


class FakeActor(FakeServing):
    def __init__(self, size=1, capacity=4, drain_factor_max=4.0, **kw):
        super().__init__(size=size, **kw)
        self._capacity = capacity
        self._drain = 1.0

    def capacity(self):
        return self._capacity

    def drain_factor(self):
        return self._drain

    def tune_drain(self):
        self.calls.append("tune_drain")
        self._drain *= 2
        return {"factor": self._drain}


def breach(ctl, rule, **fields):
    ctl.on_slo_event("slo_breach", rule=rule, value=1.0, bound=0.5,
                     **fields)


def clear(ctl, rule):
    ctl.on_slo_event("slo_clear", rule=rule, value=0.1, bound=0.5)


# ---------------------------------------------------------------------------
# Guardrails.
# ---------------------------------------------------------------------------


class TestGuardrails:
    def g(self, **kw):
        base = dict(min_size=1, max_size=3, cooldown_up_s=10.0,
                    cooldown_down_s=20.0, hold_opposite_s=30.0)
        base.update(kw)
        return Guardrails(**base)

    def test_bounds_clamp(self):
        g = self.g()
        assert g.check("up", 3, now=0.0) == "at_max"
        assert g.check("down", 1, now=0.0) == "at_min"
        assert g.check("up", 2, now=0.0) is None
        # Tuning actions bypass the size bounds, not the cooldowns.
        assert g.check("up", 3, now=0.0, bounded=False) is None

    def test_per_direction_cooldown(self):
        g = self.g()
        g.record("up", 0.0)
        assert g.check("up", 2, now=5.0) == "cooldown"
        assert g.check("up", 2, now=10.1) is None
        assert round(g.remaining("up", 5.0), 1) == 5.0

    def test_hold_opposite_outlasts_own_cooldown(self):
        g = self.g()
        g.record("up", 0.0)
        # Down's own cooldown never armed — the opposite-direction hold
        # is what blocks the reversal.
        assert g.check("down", 2, now=25.0) == "hold"
        assert g.check("down", 2, now=30.1) is None

    def test_busy_blocks_everything(self):
        g = self.g()
        assert g.check("up", 2, now=0.0, busy=True) == "busy"

    def test_unknown_direction_raises(self):
        with pytest.raises(ValueError):
            self.g().check("sideways", 2, now=0.0)


# ---------------------------------------------------------------------------
# Controller decisions (synthetic event streams, injected clocks).
# ---------------------------------------------------------------------------


class TestControllerDecisions:
    def ctl(self, cfg=None, serving=None, actor=None, rollup=None,
            events=None):
        emitted = events if events is not None else []
        c = AutopilotController(
            cfg or make_cfg(),
            rollup_fn=(lambda: rollup) if rollup is not None else None,
            emit=lambda name, **f: emitted.append((name, f)),
        )
        if serving is not None:
            c.attach_serving(serving)
        if actor is not None:
            c.attach_actor(actor)
        return c

    def test_scale_up_on_breach_then_cooldown_suppression(self):
        srv = FakeServing(size=1)
        events = []
        c = self.ctl(serving=srv, events=events)
        breach(c, "serving_p99_ms")
        acted = c.step(now=0.0)
        assert [a["action"] for a in acted] == ["scale_up"]
        assert srv.calls == ["up"] and srv.size() == 2
        assert [n for n, _ in events] == ["autopilot_action"]
        assert events[0][1]["rule"] == "serving_p99_ms"
        assert events[0][1]["size_from"] == 1
        assert events[0][1]["size_to"] == 2
        # Still breaching inside the cooldown: suppressed, not actuated.
        assert c.step(now=2.0) == []
        assert srv.size() == 2
        assert c.suppressed.get("serving:up:cooldown") == 1
        # Cooldown elapsed, breach still standing: one more step.
        assert [a["action"] for a in c.step(now=6.0)] == ["scale_up"]
        assert srv.size() == 3

    def test_clear_stops_scaling(self):
        srv = FakeServing(size=1)
        c = self.ctl(serving=srv)
        breach(c, "serving_p99_ms")
        c.step(now=0.0)
        clear(c, "serving_p99_ms")
        assert c.step(now=10.0) == []
        assert srv.size() == 2

    def test_bounds_clamp_at_max(self):
        srv = FakeServing(size=4)
        c = self.ctl(serving=srv)
        breach(c, "serving_qps")
        assert c.step(now=0.0) == []
        assert c.suppressed.get("serving:up:at_max") == 1
        assert srv.calls == []

    def test_busy_holds_scale_up(self):
        srv = FakeServing(size=1, busy=True)
        c = self.ctl(serving=srv)
        breach(c, "serving_p99_ms")
        assert c.step(now=0.0) == []
        assert c.suppressed.get("serving:up:busy") == 1

    def test_dry_run_is_inert(self):
        srv = FakeServing(size=1)
        events = []
        c = self.ctl(cfg=make_cfg(dry_run=True), serving=srv,
                     events=events)
        breach(c, "serving_p99_ms")
        acted = c.step(now=0.0)
        assert [a["action"] for a in acted] == ["scale_up"]
        assert acted[0]["dry_run"] is True
        assert srv.calls == [] and srv.size() == 1   # nothing actuated
        assert c.decisions == 1 and c.actions == 0
        # Cooldowns still arm: the dry run previews the REAL cadence.
        assert c.step(now=2.0) == []
        assert c.suppressed.get("serving:up:cooldown") == 1

    def test_both_fleet_independence(self):
        srv = FakeServing(size=1)
        act = FakeActor(size=1, capacity=4)
        c = self.ctl(serving=srv, actor=act)
        breach(c, "age_p95_ms")            # actor rule only
        acted = c.step(now=0.0)
        assert [a["fleet"] for a in acted] == ["actor"]
        assert act.size() == 2 and srv.size() == 1
        # A serving breach right after: its fleet's guardrails are its
        # own — the actor action did not consume serving's cooldown.
        breach(c, "serving_p99_ms")
        acted = c.step(now=0.1)
        assert [a["fleet"] for a in acted] == ["serving"]
        assert srv.size() == 2

    def test_actor_at_ceiling_is_suppressed_like_any_fleet(self):
        act = FakeActor(size=4, capacity=4)
        c = self.ctl(actor=act)
        breach(c, "age_p95_ms")
        assert c.step(now=0.0) == []
        assert c.suppressed.get("actor:up:at_max") == 1
        assert act.calls == [] and act.size() == 4

    def test_ring_occupancy_ladder_tunes_drain_before_retiring(self):
        act = FakeActor(size=3, capacity=4)
        cfg = make_cfg(drain_tune_max_factor=4.0, cooldown_down_s=1.0,
                       hold_opposite_s=0.0)
        c = self.ctl(cfg=cfg, actor=act)
        breach(c, "ring_occupancy")
        assert [a["action"] for a in c.step(now=0.0)] == ["tune_drain"]
        assert [a["action"] for a in c.step(now=2.0)] == ["tune_drain"]
        assert act.drain_factor() == 4.0
        # Ladder exhausted: only now does a worker retire.
        assert [a["action"] for a in c.step(now=4.0)] == ["scale_down"]
        assert act.size() == 2

    def test_flap_damping_hold_opposite(self):
        cfg = make_cfg(cooldown_up_s=1.0, cooldown_down_s=1.0,
                       hold_opposite_s=20.0,
                       serving_idle_qps_per_replica=5.0,
                       idle_window_s=10.0)
        srv = FakeServing(size=2)
        rollup = {"serving": {"replicas": 2, "qps": 0.5}}
        c = self.ctl(cfg=cfg, serving=srv, rollup=rollup)
        breach(c, "serving_p99_ms")
        c.step(now=0.0)
        assert srv.size() == 3
        clear(c, "serving_p99_ms")
        # Idle rule breaches (burn window: >=3 low samples), but the
        # opposite-direction hold blocks the reversal until t=20.
        for t in (1.0, 2.0, 3.0, 4.0):
            c.step(now=t)
        assert srv.size() == 3
        assert any(k == "serving:down:hold" for k in c.suppressed)
        acted = c.step(now=21.0)
        assert [a["action"] for a in acted] == ["scale_down"]
        assert acted[0]["rule"] == "serving_idle"
        assert srv.size() == 2

    def test_idle_scale_down_needs_green_up_rules(self):
        cfg = make_cfg(serving_idle_qps_per_replica=5.0,
                       hold_opposite_s=0.0, idle_window_s=10.0)
        srv = FakeServing(size=2)
        rollup = {"serving": {"replicas": 2, "qps": 0.5}}
        c = self.ctl(cfg=cfg, serving=srv, rollup=rollup)
        breach(c, "serving_p99_ms")      # an up-rule stands
        for t in (0.0, 1.0, 2.0, 3.0):
            c.step(now=t)
        # Idle is breaching by now, but the standing up-breach wins
        # (scale-up attempts, then at_max/cooldown — never a down).
        assert "down" not in srv.calls

    def test_exhausted_actuator_is_suppression_not_crash(self):
        srv = FakeServing(size=2, exhausted=True)
        c = self.ctl(serving=srv)
        breach(c, "serving_p99_ms")
        assert c.step(now=0.0) == []
        assert c.suppressed.get("serving:up:exhausted") == 1
        # No cooldown armed by a no-op: the next step retries at once.
        assert c.step(now=0.1) == []
        assert c.suppressed.get("serving:up:exhausted") == 2

    def test_unknown_rules_and_foreign_events_ignored(self):
        srv = FakeServing(size=1)
        c = self.ctl(serving=srv)
        c.on_slo_event("slo_breach", rule="endpoints_alive")
        c.on_slo_event("slo_breach", rule="no_such_rule")
        c.on_slo_event("worker_death", worker=3)
        assert c.step(now=0.0) == []
        assert srv.calls == []

    def test_state_matches_doc_schema(self):
        from ape_x_dqn_tpu.analysis.metrics_doc import doc_section_keys

        doc = doc_section_keys(
            "## Autopilot schema",
            os.path.join(REPO, "docs", "METRICS.md"))
        assert doc, "Autopilot schema doc section missing"
        c = self.ctl(serving=FakeServing(), actor=FakeActor())
        state = c.state(now=0.0)
        assert set(doc) == set(state), set(doc) ^ set(state)
        for fleet in state["fleets"].values():
            assert {"size", "min", "max", "busy", "breaching",
                    "last_action", "last_rule", "cooldown_up_s",
                    "cooldown_down_s"} == set(fleet)


# ---------------------------------------------------------------------------
# Pool elastic arithmetic (no processes spawned).
# ---------------------------------------------------------------------------


def _pool_cfg(num_workers=1, max_workers=3, num_actors=6) -> ApexConfig:
    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.mode = "process"
    cfg.actor.num_workers = num_workers
    cfg.actor.max_workers = max_workers
    cfg.actor.num_actors = num_actors
    cfg.actor.T = 100_000
    cfg.actor.flush_every = 8
    cfg.learner.min_replay_mem_size = 64
    cfg.replay.capacity = 4096
    return cfg.validate()


class TestPoolElasticArithmetic:
    def test_partition_is_carved_over_capacity_not_live_width(self):
        """worker_slice over local_capacity never moves as the live
        width changes — the growth-never-reshuffles contract."""
        from ape_x_dqn_tpu.runtime.process_actors import worker_slice

        cap, actors = 3, 6
        slices = [worker_slice(w, actors, cap) for w in range(cap)]
        assert slices == [(0, 2), (2, 4), (4, 6)]
        # Growing from 1 to 3 live workers changes NOTHING about any
        # wid's slice (they are a pure function of wid and capacity),
        # and the slices tile the global set exactly.
        assert sorted(x for lo, hi in slices for x in range(lo, hi)) \
            == list(range(actors))

    def test_pool_capacity_candidates_and_budgets(self):
        from ape_x_dqn_tpu.config import transport_budget
        from ape_x_dqn_tpu.runtime.process_actors import ProcessActorPool

        cfg = _pool_cfg(num_workers=1, max_workers=3)
        pool = ProcessActorPool(cfg, num_workers=1)
        try:
            assert pool.local_capacity == 3
            assert pool.total_workers == 3
            assert pool.live_workers() == []          # nothing spawned
            assert pool.grow_candidates() == [0, 1, 2]
            assert not pool.finished                  # pre-start guard
            # transport_budget at the LIVE width must agree with the
            # pool's live accounting as width changes (the satellite's
            # mid-run consistency pin — here at width 0 with no rings).
            acc = pool.shm_accounting()
            assert acc["ring_bytes_total"] == 0
            tb = transport_budget(cfg, num_workers=0)
            assert tb["ring_bytes_total"] == 0
            tb3 = transport_budget(cfg, num_workers=3)
            assert tb3["ring_bytes_total"] \
                == 3 * cfg.actor.xp_ring_bytes
            # Drain-budget tuning clamps at the floor and reports live.
            base = pool.drain_budget_bytes
            assert pool.set_drain_budget(base * 2) == base * 2
            assert pool.set_drain_budget(1) == 64 << 10
        finally:
            pool.stop()

    def test_max_workers_validation(self):
        cfg = _pool_cfg()
        cfg.actor.max_workers = 1        # < num_workers... num_workers=1 ok
        cfg.validate()
        cfg.actor.num_workers = 2
        with pytest.raises(ValueError, match="max_workers"):
            cfg.validate()
        cfg = _pool_cfg()
        cfg.actor.mode = "thread"
        with pytest.raises(ValueError, match="mode=process"):
            cfg.validate()
        cfg = _pool_cfg()
        cfg.actor.num_actors = 2         # capacity 3 > 2 actors
        with pytest.raises(ValueError, match="reserved worker capacity"):
            cfg.validate()


# ---------------------------------------------------------------------------
# Real process grow/retire e2e (the one spawning test).
# ---------------------------------------------------------------------------


class TestPoolGrowRetireE2E:
    def test_grow_then_clean_retire(self):
        from ape_x_dqn_tpu.runtime.process_actors import ProcessActorPool

        cfg = _pool_cfg(num_workers=1, max_workers=2, num_actors=4)
        pool = ProcessActorPool(cfg, num_workers=1, quantum=8)
        from ape_x_dqn_tpu.runtime.process_actors import (
            network_and_template,
        )
        import jax

        _, _, template = network_and_template(cfg)
        try:
            pool.start()
            pool.publish(template)
            deadline = time.monotonic() + 120.0

            def drain_until(cond, what):
                while time.monotonic() < deadline:
                    pool.supervise()
                    pool.poll(max_items=64, timeout=0.05)
                    if cond():
                        return
                raise TimeoutError(what)

            drain_until(lambda: 0 in pool.last_versions,
                        "wid 0 first chunk")
            # Post-start grow: the reserved wid comes up on the same
            # spawn path and delivers its own slice's chunks.
            assert pool.grow(1) == [1]
            assert pool.live_workers() == [0, 1]
            assert pool.shm_accounting()["ring_bytes_total"] \
                == 2 * cfg.actor.xp_ring_bytes
            drain_until(lambda: 1 in pool.last_versions,
                        "grown wid 1 first chunk")
            steps_before = pool._steps_by_worker.get(1, 0)
            assert steps_before > 0
            # Clean retire of the highest wid: drains, exits "done",
            # never a respawn, never an error, ring reclaimed.
            assert pool.retire() == 1
            drain_until(lambda: 1 in pool.finished_workers
                        and 1 not in pool._rings,
                        "retired wid 1 clean done + ring reclaim")
            assert pool.live_workers() == [0]
            assert not pool.worker_errors
            assert pool.restarts == 0
            assert pool.retired == {1}
            assert pool.transport.summary()["torn_records"] == 0
            assert pool.shm_accounting()["ring_bytes_total"] \
                == 1 * cfg.actor.xp_ring_bytes
            # The freed slot is a grow candidate again (remaining-budget
            # arithmetic: it consumed steps, so its budget shrank).
            assert pool.grow_candidates() == [1]
            assert pool._steps_by_worker[1] >= steps_before
        finally:
            pool.stop()
