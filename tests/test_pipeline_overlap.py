"""Overlapped dispatch pipeline (ISSUE 5): equivalence, accounting, and
runtime wiring.

The load-bearing test is strict-vs-overlapped **bit-for-bit equivalence**:
pipeline_depth > 1 changes WHERE host work happens (stager thread, folded
ingest dispatch, deferred drains) but must not change a single bit of the
params, the replay ring, or the priorities — the overlap is free lunch,
not a semantics knob.
"""

from __future__ import annotations

import io
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.learner.train_step import (
    build_train_step,
    init_train_state,
    make_optimizer,
)
from ape_x_dqn_tpu.models.dueling import build_network
from ape_x_dqn_tpu.runtime.fused_learner import FusedDeviceLearner
from ape_x_dqn_tpu.runtime.infeed import DispatchPipeline
from ape_x_dqn_tpu.types import NStepTransition

OBS = (8, 8, 1)
A = 3


def _mk_learner(seed=0, K=4, B=8, C=256, block=32):
    net = build_network("mlp", A)
    opt = make_optimizer("adam", learning_rate=1e-3)
    state = init_train_state(
        net, opt, jax.random.PRNGKey(seed), jnp.zeros((1, *OBS), jnp.uint8)
    )
    return FusedDeviceLearner(
        net, opt, state, OBS, capacity=C, batch_size=B,
        steps_per_call=K, ingest_block=block, target_sync_freq=8,
        sample_ahead=True,
    )


def _chunk(rng, m):
    return (
        (np.abs(rng.normal(size=m)) + 0.1).astype(np.float32),
        NStepTransition(
            obs=rng.integers(0, 255, (m, *OBS), dtype=np.uint8),
            action=rng.integers(0, A, (m,), dtype=np.int32),
            reward=rng.normal(size=(m,)).astype(np.float32),
            discount=np.full((m,), 0.97, np.float32),
            next_obs=rng.integers(0, 255, (m, *OBS), dtype=np.uint8),
        ),
    )


def _assert_trees_equal(a, b, what):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), what)


class TestStrictVsOverlappedEquivalence:
    def test_depth_gt_1_is_bit_for_bit_identical_to_strict(self):
        """Same seed, same chunk arrivals: strict (ingest inline, force
        every call) vs overlapped (stager split, folded last block,
        depth-3 window drained at the end) produce identical params,
        ring contents, priorities (mass), and staged leftovers."""
        chunks = [_chunk(np.random.default_rng(100 + r), 48)
                  for r in range(6)]

        strict = _mk_learner()
        for prio, trans in chunks:
            strict.add_chunk(prio, trans)
            strict.ingest_staged()
            m = strict.train(0.4)
            float(np.asarray(m.loss)[-1])  # force, strict-style

        over = _mk_learner()
        pipe = DispatchPipeline(3, probe_fn=lambda m: m.loss)
        for prio, trans in chunks:
            over.add_chunk(prio, trans)
            over.prepare_staged()  # the stager thread's half, inline here
            blocks = over.pop_prepared()
            fold = None
            if blocks and over.supports_ingest_fold \
                    and len(blocks[-1][0]) == 32:
                fold = blocks.pop()
            for blk in blocks:
                over.add_block(*blk)
            if fold is not None:
                pipe.dispatch(
                    lambda: over.train_with_ingest(0.4, fold[0], fold[1]),
                    over.steps_per_call,
                )
            else:
                pipe.dispatch(lambda: over.train(0.4), over.steps_per_call)
        pipe.sync()

        _assert_trees_equal(
            jax.device_get(strict.state), jax.device_get(over.state),
            "train state diverged",
        )
        sa, sb = strict.state_dict(), over.state_dict()
        assert set(sa) == set(sb)
        for k in sa:
            np.testing.assert_array_equal(
                np.asarray(sa[k]), np.asarray(sb[k]), f"ring field {k}"
            )
        assert strict.size == over.size
        assert strict.staged_rows == over.staged_rows

    def test_fold_is_identical_to_separate_add_then_train(self):
        """train_with_ingest (one dispatch) == add_block + train (two) —
        the fold saves a round trip, not a bit."""
        prio, trans = _chunk(np.random.default_rng(7), 32)
        warm = [_chunk(np.random.default_rng(8), 32)]

        def run(folded: bool):
            le = _mk_learner(seed=3)
            for p, t in warm:
                le.add_chunk(p, t)
                le.ingest_staged()
            if folded:
                m = le.train_with_ingest(0.4, prio, trans)
            else:
                le.add_block(prio, trans)
                m = le.train(0.4)
            np.asarray(m.loss)
            return jax.device_get(le.state), le.state_dict()

        (s1, r1), (s2, r2) = run(False), run(True)
        _assert_trees_equal(s1, s2, "fold changed the train state")
        for k in r1:
            np.testing.assert_array_equal(
                np.asarray(r1[k]), np.asarray(r2[k]), f"ring field {k}"
            )

    def test_fold_rejects_partial_block(self):
        le = _mk_learner()
        prio, trans = _chunk(np.random.default_rng(9), 16)
        with pytest.raises(ValueError, match="full ingest_block"):
            le.train_with_ingest(0.4, prio, trans)


class TestPreparedStaging:
    def test_prepared_rows_still_ride_staged_rows_and_snapshots(self):
        """A block that was carved but not yet dispatched must stay
        visible to checkpointing — prepare_staged moves rows between
        stages of the double buffer, it must not leak them."""
        le = _mk_learner()
        prio, trans = _chunk(np.random.default_rng(1), 40)
        le.add_chunk(prio, trans)
        assert le.staged_rows == 40
        le.prepare_staged()
        assert le.staged_rows == 40  # 32 prepared + 8 staged tail
        snap = le.state_dict()
        assert len(snap["staged_prio"]) == 40
        np.testing.assert_array_equal(snap["staged_prio"], prio)

    def test_prepare_then_dispatch_matches_inline_ingest(self):
        rng = np.random.default_rng(2)
        prio, trans = _chunk(rng, 80)
        a, b = _mk_learner(), _mk_learner()
        a.add_chunk(prio, trans)
        a.ingest_staged(drain=True)
        b.add_chunk(prio, trans)
        b.prepare_staged(drain=True)
        ingested = sum(b.add_block(*blk) for blk in b.pop_prepared())
        assert ingested == a.size == b.size
        for k, v in a.state_dict().items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(b.state_dict()[k]), k
            )


class _FakeProbe:
    """Duck-typed jax.Array stand-in with controllable readiness."""

    def __init__(self, ready=False):
        self.ready = ready
        self.copies = 0

    def is_ready(self):
        return self.ready

    def copy_to_host_async(self):
        self.copies += 1

    def __array__(self, dtype=None, copy=None):
        return np.zeros(1, np.float32)


class _GapSink:
    def __init__(self):
        self.values = []

    def observe(self, v):
        self.values.append(v)


class TestDispatchPipelineUnit:
    def test_strict_depth1_counts_a_sync_per_unready_call(self):
        pipe = DispatchPipeline(1, probe_fn=lambda p: p)
        for _ in range(5):
            pipe.dispatch(lambda: _FakeProbe(ready=False), steps=4)
        assert pipe.host_syncs == 5
        assert len(pipe) == 0

    def test_ready_calls_retire_free(self):
        pipe = DispatchPipeline(1, probe_fn=lambda p: p)
        for _ in range(5):
            pipe.dispatch(lambda: _FakeProbe(ready=True), steps=4)
        assert pipe.host_syncs == 0

    def test_depth_window_polls_instead_of_blocking(self):
        """At depth>1 a full window waits by polling; a probe that turns
        ready during the poll retires with NO counted sync."""
        pipe = DispatchPipeline(2, probe_fn=lambda p: p,
                                poll_s=1e-4, poll_deadline_s=5.0)
        probes = []

        def make():
            p = _FakeProbe(ready=False)
            probes.append(p)
            return p

        pipe.dispatch(make, steps=1)  # len 1 < depth: no wait

        import threading

        def release():
            time.sleep(0.05)
            probes[0].ready = True

        t = threading.Thread(target=release)
        t.start()
        # This dispatch fills the window (len == depth) and poll-waits on
        # the oldest until the release thread flips it ready.
        pipe.dispatch(make, steps=1)
        t.join()
        assert pipe.host_syncs == 0
        assert len(pipe) == 1

    def test_poll_deadline_degrades_to_counted_block(self):
        pipe = DispatchPipeline(2, probe_fn=lambda p: p,
                                poll_s=1e-4, poll_deadline_s=0.02)
        pipe.dispatch(lambda: _FakeProbe(ready=False), steps=1)
        # Fills the window; the oldest never turns ready, the deadline
        # blows, and the hard block is counted.
        pipe.dispatch(lambda: _FakeProbe(ready=False), steps=1)
        assert pipe.host_syncs == 1

    def test_sync_counts_one_event_per_burst(self):
        pipe = DispatchPipeline(8, probe_fn=lambda p: p)
        for _ in range(4):
            pipe.dispatch(lambda: _FakeProbe(ready=False), steps=1)
        assert pipe.sync() == 4
        assert pipe.host_syncs == 1       # one burst, one sync
        for _ in range(3):
            pipe.dispatch(lambda: _FakeProbe(ready=True), steps=1)
        pipe.drain_ready()
        assert pipe.sync() == 0           # nothing left -> free
        assert pipe.host_syncs == 1

    def test_gap_recorded_when_device_idles(self):
        gaps = _GapSink()
        pipe = DispatchPipeline(4, probe_fn=lambda p: p, gap_hist_ms=gaps)
        pipe.dispatch(lambda: _FakeProbe(ready=True), steps=1)
        time.sleep(0.02)
        pipe.dispatch(lambda: _FakeProbe(ready=False), steps=1)
        # Newest (the ready probe) had landed before this dispatch: idle.
        assert gaps.values and gaps.values[-1] >= 10.0  # ms
        pipe.dispatch(lambda: _FakeProbe(ready=False), steps=1)
        # Newest not ready -> device busy -> 0 gap.
        assert gaps.values[-1] == 0.0

    def test_steps_accounting_via_on_retire(self):
        seen = []
        pipe = DispatchPipeline(
            4, probe_fn=lambda p: p,
            on_retire=lambda m, s: seen.append(s),
        )
        for _ in range(6):
            pipe.dispatch(lambda: _FakeProbe(ready=True), steps=16)
        pipe.sync()
        assert sum(seen) == 96
        assert pipe.steps_inflight == 0


class TestOverlappedRuntime:
    def _cfg(self, depth, sync_every, steps):
        from ape_x_dqn_tpu.config import ApexConfig

        cfg = ApexConfig()
        cfg.network = "mlp"
        cfg.env.name = "random:8x8x1"
        cfg.actor.num_actors = 4
        cfg.actor.T = 1_000_000
        cfg.actor.flush_every = 8
        cfg.learner.device_replay = True
        cfg.learner.sample_ahead = True
        cfg.learner.steps_per_call = 32
        cfg.learner.ingest_block = 64
        cfg.learner.min_replay_mem_size = 128
        cfg.learner.publish_every = 128
        cfg.learner.total_steps = steps
        cfg.learner.pipeline_depth = depth
        cfg.learner.sync_every = sync_every
        cfg.replay.capacity = 2048
        return cfg.validate()

    def test_overlapped_fused_run_end_to_end(self):
        from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
        from ape_x_dqn_tpu.utils.metrics import MetricLogger

        buf = io.StringIO()
        pipe = AsyncPipeline(
            self._cfg(depth=2, sync_every=64, steps=256),
            logger=MetricLogger(stream=buf), log_every=128,
        )
        final = pipe.run(learner_steps=256, warmup_timeout=120.0)
        assert final["step"] >= 256
        assert np.isfinite(final["learner/loss"])
        p = final["pipeline"]
        assert p["depth"] == 2 and p["sync_every"] == 64
        assert p["inflight"] == 0, "flush-at-exit left calls in flight"
        assert p["gaps_observed"] > 0
        # The JSONL stream carries the same section.
        lines = [json.loads(l) for l in buf.getvalue().splitlines()]
        periodic = [r for r in lines if "pipeline" in r]
        assert periodic, "pipeline section missing from the JSONL stream"
        # /varz carries the instruments.
        snap = pipe.obs_registry.snapshot()
        assert "learner/host_syncs" in snap
        assert "learner/overlap_gap_ms" in snap
        # The stager thread's staging is a stage of its own.
        assert pipe.timers.snapshot()["ingest_prepare"]["calls"] >= 1
        assert "ingest_prepare" in final["stage_us"]

    def test_stager_counts_only_polls_that_staged_rows(self):
        """``ingest_prepare``'s mean is per staging, not per 5 ms poll: an
        empty poll leaves a span in a trace and nothing in the counters."""
        import threading
        import time

        from ape_x_dqn_tpu.runtime.async_pipeline import _IngestStagerThread
        from ape_x_dqn_tpu.utils.profiling import StageTimer

        script = [0, 64, 0, 0, 128, 0]

        class Fused:
            polls = 0

            def prepare_staged(self, drain=False):
                self.polls += 1
                return script.pop(0) if script else 0

        fused, timers = Fused(), StageTimer()
        stager = _IngestStagerThread(fused, threading.Event(), lambda: False,
                                     timers, period_s=0.001)
        stager.start()
        deadline = time.monotonic() + 30
        while script and time.monotonic() < deadline:
            time.sleep(0.005)
        stager.stop()
        assert stager.error is None and stager.prepared_rows == 192
        assert fused.polls >= 6
        assert timers.snapshot()["ingest_prepare"]["calls"] == 2

    def test_host_path_batched_writeback(self):
        """pipeline_depth > 1 on the HOST-replay path batches the deferred
        priority write-back; the run completes and priorities were
        committed (replay priorities moved off the init value)."""
        from ape_x_dqn_tpu.config import ApexConfig
        from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
        from ape_x_dqn_tpu.utils.metrics import MetricLogger

        cfg = ApexConfig()
        cfg.network = "mlp"
        cfg.env.name = "chain:6"
        cfg.actor.num_actors = 4
        cfg.actor.T = 1_000_000
        cfg.actor.flush_every = 8
        cfg.learner.min_replay_mem_size = 64
        cfg.learner.total_steps = 40
        cfg.learner.optimizer = "adam"
        cfg.learner.learning_rate = 1e-3
        cfg.learner.pipeline_depth = 4
        cfg.replay.capacity = 1024
        cfg.validate()
        pipe = AsyncPipeline(
            cfg, logger=MetricLogger(stream=io.StringIO()), log_every=1000,
        )
        final = pipe.run(learner_steps=40, warmup_timeout=120.0)
        assert final["step"] == 40
        assert np.isfinite(final["learner/loss"])
        # The final flush committed the tail: fewer than depth steps can
        # remain unwritten, and the tree total reflects restamps.
        assert pipe.comps.replay.size() > 0


class TestConfigKnobs:
    def test_validation(self):
        from ape_x_dqn_tpu.config import ApexConfig

        cfg = ApexConfig()
        cfg.learner.pipeline_depth = 0
        with pytest.raises(ValueError, match="pipeline_depth"):
            cfg.validate()
        cfg = ApexConfig()
        cfg.learner.sync_every = 64
        with pytest.raises(ValueError, match="sync_every"):
            cfg.validate()  # requires device_replay
        cfg.learner.device_replay = True
        cfg.validate()
