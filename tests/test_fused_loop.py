"""The trainer's one fused loop (``AsyncPipeline._run_fused``) and the
host-replay loop's deferred write-back, observed from outside: a recording
stand-in for ``pipe.fused`` notes every dispatch and every host read of a
call's loss, and wrappers note emits, rate credits, publishes and
checkpoints, all in one ordered list.

What the loop promises: at most two fused calls dispatched and not yet
forced (the benchmark's feed holds the same two), every call forced before
``run()`` ends, steps credited to ``steps_per_sec`` when a call is forced,
publishes and checkpoints once per call that crosses their cadence, a
non-finite loss is an error, and the run stops on a call boundary.
"""

from __future__ import annotations

import io
import types

import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.config import ApexConfig
from ape_x_dqn_tpu.runtime.async_pipeline import AsyncPipeline
from ape_x_dqn_tpu.utils.metrics import MetricLogger

K = 8


class _Loss:
    """Stands where a fused call's device loss stands: converting any part
    of it to numpy is the host read that forces the call."""

    def __init__(self, arr, on_read):
        self._arr, self._on_read = arr, on_read

    def __getitem__(self, i):
        return _Loss(self._arr[i], self._on_read)

    def __array__(self, dtype=None, copy=None):
        self._on_read()
        return np.asarray(self._arr, dtype)


class _RecordingFused:
    """``pipe.fused`` with ``train`` recorded; the rest passes through."""

    def __init__(self, inner, events, nan_at=None):
        self._inner, self._events, self._nan_at = inner, events, nan_at
        self.calls = 0
        self.forced: set = set()

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def train(self, beta):
        i = self.calls
        self.calls += 1
        self._events.append(("dispatch", i))
        m = self._inner.train(beta)

        def on_read():
            if i not in self.forced:
                self.forced.add(i)
                self._events.append(("force", i))

        loss = jnp.full_like(m.loss, jnp.nan) if i == self._nan_at else m.loss
        return types.SimpleNamespace(
            loss=_Loss(loss, on_read), mean_q=m.mean_q, routing=m.routing)


def _fused_cfg(**learner):
    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "random:8x8x1"
    cfg.actor.num_actors = 4
    cfg.actor.T = 1_000_000
    cfg.actor.flush_every = 8
    cfg.learner.device_replay = True
    cfg.learner.sample_ahead = True
    cfg.learner.steps_per_call = K
    cfg.learner.ingest_block = 64
    cfg.learner.min_replay_mem_size = 128
    cfg.learner.publish_every = 64
    cfg.replay.capacity = 2048
    for name, value in learner.items():
        setattr(cfg.learner, name, value)
    return cfg


def _recorded_run(cfg, target, nan_at=None, log_every=3 * K):
    """Run the fused loop to ``target`` with everything recorded.  Returns
    (events, final record, pipe)."""
    cfg.learner.total_steps = target
    pipe = AsyncPipeline(cfg.validate(),
                         logger=MetricLogger(stream=io.StringIO()),
                         log_every=log_every)
    events: list = []
    pipe.fused = _RecordingFused(pipe.fused, events, nan_at=nan_at)

    def record(owner, name, event):
        inner = getattr(owner, name)

        def wrapped(*a, **kw):
            events.append(event(*a, **kw))
            return inner(*a, **kw)

        setattr(owner, name, wrapped)

    record(pipe.logger, "emit",
           lambda **kw: ("emit", bool(kw.get("final")), kw["step"]))
    record(pipe._steps_rate, "add", lambda n=1.0: ("credit", n))
    record(pipe, "_publish", lambda params: ("publish", pipe.learner_step))
    # The cadence is the loop's; what a save writes is test_checkpoint's.
    pipe._save_fused_checkpoint = lambda: events.append(
        ("ckpt", pipe.learner_step))
    final = pipe.run(learner_steps=target, warmup_timeout=120.0)
    return events, final, pipe


def _unforced_at_each_dispatch(events):
    out, dispatched, forced = [], 0, 0
    for ev in events:
        if ev[0] == "dispatch":
            dispatched += 1
            out.append(dispatched - forced)
        elif ev[0] == "force":
            forced += 1
    return out


@pytest.fixture(scope="module")
def thread_run():
    # 8 calls, a target that is no multiple of K, an emit every third call.
    return _recorded_run(_fused_cfg(), target=7 * K + 3)


def test_at_most_two_calls_unforced_at_any_dispatch(thread_run):
    events, _, pipe = thread_run
    unforced = _unforced_at_each_dispatch(events)
    assert len(unforced) == pipe.fused.calls == 8
    assert max(unforced) == 2, unforced
    # ... and it does hold two: the next call is queued behind the running
    # one, the device does not wait for the host.
    assert unforced[1:].count(2) >= len(unforced) // 2, unforced


def test_process_actors_hold_the_same_two():
    cfg = _fused_cfg()
    cfg.actor.mode = "process"
    cfg.actor.num_workers = 1
    events, final, pipe = _recorded_run(cfg, target=4 * K)
    unforced = _unforced_at_each_dispatch(events)
    assert len(unforced) == 4 and max(unforced) == 2, unforced
    assert pipe.fused.forced == set(range(4))
    assert final["step"] == 4 * K and np.isfinite(final["learner/loss"])


def test_every_call_is_forced_before_the_final_emit(thread_run):
    events, final, pipe = thread_run
    assert pipe.fused.forced == set(range(pipe.fused.calls))
    assert final["final"] is True
    last = [i for i, ev in enumerate(events) if ev[0] == "emit" and ev[1]]
    assert len(last) == 1
    assert not any(ev[0] in ("dispatch", "force") for ev in events[last[0]:])


def test_steps_are_credited_when_a_call_is_forced_not_dispatched(thread_run):
    events, _, pipe = thread_run
    credited = forced = 0
    for ev in events:
        if ev[0] == "force":
            forced += 1
        elif ev[0] == "credit":
            assert ev[1] == K
            credited += 1
            assert credited <= forced, "steps credited for an unforced call"
    assert credited == pipe.fused.calls


def test_run_ends_on_the_first_call_boundary_at_or_past_the_target(thread_run):
    _, final, pipe = thread_run
    assert final["step"] == pipe.learner_step == 8 * K  # target 7K+3
    assert pipe.fused.step == 8 * K


@pytest.mark.parametrize("publish_every,want", [
    (2, [K * i for i in range(1, 11)]),          # finer than K: every call
    (20, [24, 40, 64, 80]),                      # coarser: calls that cross it
], ids=["finer_than_K", "coarser_than_K"])
def test_publish_once_per_call_that_crosses_the_cadence(publish_every, want):
    events, _, pipe = _recorded_run(
        _fused_cfg(publish_every=publish_every), target=10 * K)
    assert [ev[1] for ev in events if ev[0] == "publish"] == want
    assert pipe.store.version >= 1


@pytest.mark.parametrize("checkpoint_every,want", [
    (K // 2, [K * i for i in range(1, 7)]),      # finer than K: one a call
    (3 * K, [3 * K, 6 * K]),                     # coarser: one a crossing
], ids=["finer_than_K", "coarser_than_K"])
def test_one_checkpoint_per_call_that_crosses_the_cadence(
        checkpoint_every, want, tmp_path):
    events, _, _ = _recorded_run(
        _fused_cfg(checkpoint_every=checkpoint_every,
                   checkpoint_dir=str(tmp_path / "ckpt")), target=6 * K)
    assert [ev[1] for ev in events if ev[0] == "ckpt"] == want


def test_non_finite_loss_raises():
    with pytest.raises(FloatingPointError, match="non-finite loss"):
        _recorded_run(_fused_cfg(), target=3 * K, nan_at=2,
                      log_every=100 * K)


@pytest.mark.parametrize("rows", [1, 5, 31, 32, 33, 47, 63, 100])
def test_drain_carves_the_tail_into_power_of_two_blocks(rows):
    from tests.test_fused_runtime import make_learner, np_chunk

    block = 32
    fl = make_learner(ingest_block=block, capacity=256)
    added: list = []
    inner = fl._add

    def add(ring, transitions, priorities):
        added.append(len(priorities))
        return inner(ring, transitions, priorities)

    fl._add = add
    chunk = np_chunk(rows, seed=rows)
    fl.add_chunk(np.arange(1, rows + 1, dtype=np.float32), chunk)
    assert fl.ingest_staged(drain=True) == rows
    assert fl.staged_rows == 0 and fl.size == rows and sum(added) == rows
    full, tail = added[:rows // block], added[rows // block:]
    assert full == [block] * (rows // block)
    # the tail: distinct powers of two, largest first (at most log2(block)
    # compiled shapes, no padding rows in the ring)
    assert all(n & (n - 1) == 0 and n < block for n in tail), added
    assert tail == sorted(set(tail), reverse=True), added
    np.testing.assert_array_equal(np.asarray(fl._replay.obs)[:rows], chunk.obs)


def test_host_path_writes_priorities_back_one_step_behind():
    cfg = ApexConfig()
    cfg.network = "mlp"
    cfg.env.name = "chain:6"
    cfg.actor.num_actors = 4
    cfg.actor.T = 1_000_000
    cfg.actor.flush_every = 8
    cfg.learner.min_replay_mem_size = 64
    cfg.learner.total_steps = 12
    cfg.learner.optimizer = "adam"
    cfg.learner.learning_rate = 1e-3
    cfg.replay.capacity = 1024
    pipe = AsyncPipeline(cfg.validate(),
                         logger=MetricLogger(stream=io.StringIO()),
                         log_every=1000)
    placed, written = [], []
    place, write_back = pipe._place, pipe._write_back_priorities

    def recording_place(host_batch):
        out = place(host_batch)
        placed.append(out[0])
        return out

    def recording_write_back(idx, priorities):
        written.append((pipe.learner_step, idx))
        return write_back(idx, priorities)

    pipe._place = recording_place
    pipe._write_back_priorities = recording_write_back
    final = pipe.run(learner_steps=12, warmup_timeout=120.0)
    assert final["step"] == 12 and np.isfinite(final["learner/loss"])
    # step i's priorities land while step i+1 is in flight; the last
    # step's at exit, so none is left unwritten.
    assert [at for at, _ in written] == list(range(2, 13)) + [12]
    for i, (_, idx) in enumerate(written):
        np.testing.assert_array_equal(idx, placed[i])
