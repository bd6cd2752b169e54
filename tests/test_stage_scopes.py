"""The stage vocabulary of utils/profiling.py: the scopes the fused builders
emit and the HLO text that holds them (``fused_hlo_text``), the host stages
as ``apex:<stage>`` profiler spans, and the reducer behind /varz?trace=1 —
all on the CPU at toy widths."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.learner.train_step import (
    build_train_step,
    init_train_state,
    make_optimizer,
)
from ape_x_dqn_tpu.models.dueling import DuelingMLP
from ape_x_dqn_tpu.parallel import make_mesh
from ape_x_dqn_tpu.utils import profiling

OBS = (8,)
B, K, C = 8, 3, 64


def _learner(axis=None):
    net = DuelingMLP(num_actions=3, hidden_sizes=(16,))
    opt = make_optimizer("rmsprop", learning_rate=1e-3)
    step = build_train_step(net, opt, sync_in_step=False, jit=False,
                            grad_reduce_axis=axis)
    state = init_train_state(net, opt, jax.random.PRNGKey(0),
                             jnp.zeros((1, *OBS), jnp.uint8))
    return step, state


def _chunk(m):
    from ape_x_dqn_tpu.types import NStepTransition

    r = np.random.default_rng(0)
    return NStepTransition(
        obs=r.integers(0, 255, (m, *OBS), dtype=np.uint8),
        action=r.integers(0, 3, (m,), dtype=np.int32),
        reward=r.normal(size=(m,)).astype(np.float32),
        discount=np.full((m,), 0.9, np.float32),
        next_obs=r.integers(0, 255, (m, *OBS), dtype=np.uint8),
    )


def _call_double_store(ahead):
    from ape_x_dqn_tpu.replay.device import (
        build_fused_learn_step, device_replay_add, init_device_replay,
    )

    step, state = _learner()
    ring = device_replay_add(init_device_replay(C, OBS), _chunk(C), jnp.ones(C))
    fused = build_fused_learn_step(step, B, steps_per_call=K, target_sync_freq=K,
                                   include_ingest=True, sample_ahead=ahead)
    fused(state, ring, _chunk(16), jnp.ones(16), 0.4, jax.random.PRNGKey(1))
    return fused, "jit_fused", True


def _fill_dedup(ring, add_frames, add_txns, n=1):
    m = C // n
    ref = np.tile(np.arange(m, dtype=np.int32), (n, 1))
    tile = lambda a: np.tile(a, (n,) + (1,) * a.ndim)  # noqa: E731
    r = np.random.default_rng(0)
    frames = r.integers(0, 255, (m + 1, *OBS), dtype=np.uint8)
    sq = (lambda a: a[0]) if n == 1 else (lambda a: a)  # one ring: no shard axis
    ring = add_frames(ring, sq(tile(frames)))
    return add_txns(ring, sq(ref), sq(ref + 1), sq(tile(np.zeros(m, np.int32))),
                    sq(tile(np.ones(m, np.float32))),
                    sq(tile(np.full(m, 0.9, np.float32))),
                    sq(tile(np.ones(m, np.float32))))


def _call_dedup(ahead):
    from ape_x_dqn_tpu.replay.device_dedup import (
        build_dedup_fused_learn_step, dedup_device_add_frames,
        dedup_device_add_transitions, init_dedup_device_replay,
    )

    step, state = _learner()
    ring = _fill_dedup(init_dedup_device_replay(C, OBS), dedup_device_add_frames,
                       dedup_device_add_transitions)
    fused = build_dedup_fused_learn_step(step, B, steps_per_call=K,
                                         target_sync_freq=K, sample_ahead=ahead)
    fused(state, ring, 0.4, jax.random.PRNGKey(1))
    return fused, "jit_fused", False


def _replicated(mesh, state):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(state, NamedSharding(mesh, P()))


def _call_sharded(ahead):
    from ape_x_dqn_tpu.replay.device_dp import (
        build_sharded_fused_learn_step, build_sharded_replay_add,
        init_sharded_device_replay,
    )

    mesh = make_mesh(num_devices=4)
    step, state = _learner("data")
    ring = build_sharded_replay_add(mesh)(
        init_sharded_device_replay(C, OBS, mesh), jax.device_put(_chunk(C)), jnp.ones(C))
    fused = build_sharded_fused_learn_step(step, mesh, B, steps_per_call=K,
                                           target_sync_freq=K, sample_ahead=ahead)
    fused(_replicated(mesh, state), ring, 0.4, jax.random.PRNGKey(1))
    return fused, "jit_body", False


def _call_sharded_dedup(ahead):
    from ape_x_dqn_tpu.replay.device_dedup_dp import (
        build_sharded_dedup_add_frames, build_sharded_dedup_add_transitions,
        build_sharded_dedup_fused_learn_step, init_sharded_dedup_replay,
    )

    mesh = make_mesh(num_devices=4)
    step, state = _learner("data")
    ring = _fill_dedup(init_sharded_dedup_replay(C, OBS, mesh),
                       build_sharded_dedup_add_frames(mesh),
                       build_sharded_dedup_add_transitions(mesh), n=4)
    fused = build_sharded_dedup_fused_learn_step(step, mesh, B, steps_per_call=K,
                                                 target_sync_freq=K, sample_ahead=ahead)
    fused(_replicated(mesh, state), ring, 0.4, jax.random.PRNGKey(1))
    return fused, "jit_body", False


@pytest.mark.parametrize("ahead", [True, False], ids=["sample_ahead", "strict"])
@pytest.mark.parametrize("call", [_call_double_store, _call_dedup, _call_sharded,
                                  _call_sharded_dedup],
                         ids=["double_store", "dedup", "sharded", "sharded_dedup"])
def test_every_stage_is_named_in_the_fused_programs_text(call, ahead):
    fused, name, ingests = call(ahead)
    # the name a run carries in a device trace is what it was before the scopes
    assert "jit_" + fused.__wrapped__.__name__ == name
    text = profiling.fused_hlo_text(name)
    assert text.startswith("HloModule " + name)
    stages = set(profiling.hlo_stages(text).values())
    want = set(profiling.STAGES) - (set() if ingests else {"ingest"})
    assert want <= stages, want - stages
    assert "jvp(stage:forward)" in text and "transpose(jvp(stage:forward))" in text
    # the text is that of the program that ran: lowered from the signature the
    # trace recorded, it meets jit's own executable cache
    prog = profiling._fused_programs[name][-1]
    assert prog.jitted is fused and prog.signature is not None
    assert list(profiling.fused_hlo_texts(name))[-1] is text  # kept, not made again


def test_stage_refuses_a_name_outside_the_vocabulary():
    with profiling.stage("gather"):
        pass
    with pytest.raises(ValueError, match="unknown stage"):
        profiling.stage("gathering")
    with pytest.raises(KeyError, match="jit_never_built"):
        profiling.fused_hlo_text("jit_never_built")


HLO = """HloModule jit_toy, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %n = f32[4]{0} negate(%p), metadata={op_name="jit(toy)/stage:sample/neg"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%t), index=1
  %slice.0 = f32[4]{0} dynamic-slice(%x), metadata={op_name="jit(toy)/while/body/dynamic_slice"}
  %fusion.1 = f32[4]{0} fusion(%slice.0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(toy)/while/body/closed_call/jvp(stage:forward)/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1, %slice.0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(toy)/while/body/closed_call/transpose(jvp(stage:forward))/dot_general"}
  %add.3 = f32[4]{0} add(%fusion.2, %x), metadata={op_name="jit(toy)/while/body/closed_call/stage:optimizer/add"}
  %i = s32[] get-tuple-element(%t), index=0
  ROOT %out = (s32[], f32[4]{0}) tuple(%i, %add.3)
}

ENTRY %main (ring: f32[4], w: f32[4]) -> f32[4] {
  %ring = f32[4]{0} parameter(0), metadata={op_name="ring"}
  %copy.7 = f32[4]{0} copy(%ring)
  %bitcast.8 = f32[4]{0} bitcast(%copy.7)
  %fusion.9 = f32[4]{0} fusion(%bitcast.8), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(toy)/stage:gather/gather"}
  %copy.10 = f32[4]{0} copy(%ring)
  %fusion.11 = f32[4]{0} fusion(%copy.10), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(toy)/stage:sample/reduce_sum"}
  %fusion.12 = f32[4]{0} fusion(%copy.10), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(toy)/stage:restamp/scatter"}
  %zero = s32[] constant(0)
  %copy.17 = f32[4]{0} copy(%fusion.9)
  %init = (s32[], f32[4]{0}) tuple(%zero, %copy.17)
  %while.13 = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(toy)/while"}
  %res = f32[4]{0} get-tuple-element(%while.13), index=1
  %fusion.14 = f32[4]{0} fusion(%res), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(toy)/stage:target_sync/select_n"}
  %fusion.15 = f32[4]{0} fusion(%res, %fusion.12), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(toy)/stage:restamp/scatter"}
  %copy.16 = f32[4]{0} copy(%fusion.9)
  ROOT %outs = (f32[4]{0}, f32[4]{0}, f32[4]{0}) tuple(%fusion.14, %fusion.15, %copy.16)
}
"""


def test_hlo_stages_reads_each_instructions_own_scope_and_nothing_else():
    st = profiling.hlo_stages(HLO)
    assert st["fusion.1"] == "forward" and st["fusion.2"] == "backward"
    assert st["add.3"] == "optimizer" and st["fusion.9"] == "gather"
    assert st["n"] == "sample" and st["fusion.14"] == "target_sync"
    # no scope of its own: `other`, whatever consumes it (the benchmark's
    # reader hands these on; tests/benchmark holds the two together)
    assert {st[n] for n in ("copy.7", "bitcast.8", "copy.10", "while.13",
                            "slice.0", "copy.16", "copy.17")} == {"other"}
    assert "fusion.99" not in st


def test_stage_timer_stage_is_also_a_trace_annotation(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    timers = profiling.StageTimer()
    with timers.stage("ingest"):
        pass
    with pytest.raises(RuntimeError):
        with timers.stage("fused_dispatch"):
            raise RuntimeError("dispatch failed")
    assert entered == [("enter", "apex:ingest"), ("exit", "apex:ingest"),
                       ("enter", "apex:fused_dispatch"), ("exit", "apex:fused_dispatch")]
    us = timers.us_per_call()
    assert set(us) == {"ingest", "fused_dispatch"}
    assert timers._count["fused_dispatch"] == 1
    assert us["fused_dispatch"] >= 0.0 and us["ingest"] >= 0.0


def _fake_profile(ops, modules, spans):
    """What ``ProfileData.from_file`` returns, as far as the reducer reads it:
    one TPU plane with an ``XLA Ops`` line and the host plane."""
    def ev(name, start_us, dur_us):
        return types.SimpleNamespace(name=name, start_ns=start_us * 1e3,
                                     duration_ns=dur_us * 1e3)

    def plane(name, **lines):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=n, events=[ev(*e) for e in evs])
            for n, evs in lines.items()])

    return types.SimpleNamespace(planes=[
        plane("/device:TPU:0", **{"XLA Ops": ops, "XLA Modules": modules}),
        plane("/host:CPU", learner=spans),
    ])


@pytest.fixture
def stubbed_trace(tmp_path, monkeypatch):
    """A trace on disk that is never written: the xplane is an empty file,
    ``ProfileData`` hands back hand-made planes, and the program's registry
    holds one fused program whose text is ``HLO``."""
    import contextlib

    from jax.profiler import ProfileData

    ops = [("%copy.7 = f32[4]{0} copy(%ring)", 0, 40),
           ("%fusion.9 = f32[4]{0} fusion(%bitcast.8)", 40, 10),
           ("%while.13 = (s32[], f32[4]{0}) while(%init)", 50, 100),
           ("%fusion.1 = f32[4]{0} fusion(%x)", 55, 30),
           ("%fusion.2 = f32[4]{0} fusion(%fusion.1)", 85, 50),
           ("%add.3 = f32[4]{0} add(%fusion.2, %x)", 135, 10),
           ("%fusion.99 = f32[4]{0} fusion(%q)", 150, 10),    # not in the text
           ("%fusion.1 = f32[8]{0} fusion(%obs)", 1150, 40)]  # an actor's program
    modules = [("jit_toy(77)", 0, 160), ("jit_apply(5)", 1150, 40)]
    spans = [("apex:ingest", 140, 900), ("apex:fused_dispatch", 1040, 20),
             ("python:other", 0, 2000)]
    monkeypatch.setattr(ProfileData, "from_file",
                        staticmethod(lambda path: _fake_profile(ops, modules, spans)))

    @contextlib.contextmanager
    def fake_trace(logdir):
        d = tmp_path / "log" / "plugins" / "profile" / "run1"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        yield

    monkeypatch.setattr(profiling, "trace", fake_trace)
    prog = profiling._FusedProgram(lambda: None, {})
    prog.signature, prog.text = (), HLO
    monkeypatch.setitem(profiling._fused_programs, "jit_toy", [prog])
    return str(tmp_path / "log")


def test_summarize_trace_gives_seconds_per_stage_and_names_gaps(stubbed_trace):
    with profiling.trace(stubbed_trace):
        pass
    s = profiling.summarize_trace(stubbed_trace)
    us = {k: round(v * 1e6, 3) for k, v in s["stage_s"].items()}
    # the unscoped ring copy, the while's own 10 us and the op the text does
    # not hold are `other`; an op of another program is not the fused
    # learner's, whatever its name
    assert us == {"gather": 10.0, "forward": 30.0, "backward": 50.0,
                  "optimizer": 10.0, "other": 60.0, "other_programs": 40.0}
    assert s["stage_named_share"] == pytest.approx(150 / 160)  # fusion.99 is not in the text
    assert s["device_busy_share"] == pytest.approx(200 / 1190)
    assert s["longest_gaps"] == [["apex:ingest", pytest.approx(990e-6)]]
    assert s["host_spans"] == 2 and s["devices"] == 1


def test_varz_trace_summary_holds_per_stage_seconds(stubbed_trace):
    from ape_x_dqn_tpu.obs.trace import TraceOnDemand

    steps = iter(range(0, 10_000, 40))
    tod = TraceOnDemand(step_fn=lambda: next(steps), steps=64, out_dir=stubbed_trace)
    tod._capture(stubbed_trace, 64)
    assert tod.last["state"] == "done", tod.last
    rec = json.load(open(stubbed_trace + "/summary.json"))
    assert rec["summary"]["stage_s"]["backward"] == pytest.approx(50e-6)
    assert rec["summary"]["longest_gaps"][0][0] == "apex:ingest"
    assert rec["steps_traced"] >= 64


def test_varz_trace_reports_a_failed_profiler_as_an_error(monkeypatch, tmp_path):
    import contextlib

    from ape_x_dqn_tpu.obs.trace import TraceOnDemand

    @contextlib.contextmanager
    def boom(logdir, **_kw):
        raise RuntimeError("profiler plugin missing")
        yield

    monkeypatch.setattr(profiling, "trace", boom)
    tod = TraceOnDemand(out_dir=str(tmp_path))
    tod._capture(str(tmp_path), 8)
    assert tod.last["state"] == "error" and "plugin missing" in tod.last["reason"]


STALE = r"""
import contextlib, os, sys, threading, time
import jax, jax.numpy as jnp
from ape_x_dqn_tpu.utils import profiling
from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache()
if sys.argv[1] == "unscoped":   # what a build before the scopes compiled
    profiling.stage = lambda name: contextlib.nullcontext()
sys.path.insert(0, os.path.dirname(sys.argv[2]))
import test_stage_scopes as t
fused, name, _ = t._call_dedup(True)
ran = fused.lower(*profiling._fused_programs[name][-1].signature).compile().as_text()
seen, done = [], threading.Event()   # what another thread's compile would be keyed with
def watch():
    while not done.is_set():
        seen.append(jax.config.jax_compilation_cache_include_metadata_in_key)
        time.sleep(0.001)
watcher = threading.Thread(target=watch); watcher.start()
text = profiling.fused_hlo_text(name)
done.set(); watcher.join()
print("RAN", "stage:" in ran, "TEXT", "stage:" in text, "ELSEWHERE", any(seen))
"""


def test_a_cache_entry_written_without_scopes_still_gives_a_text_with_them(tmp_path):
    """The persistent compile cache's key leaves metadata out: the executable a
    scoped build loads may have been compiled by an unscoped one."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    said = []
    for build in ("unscoped", "scoped", "scoped"):
        p = subprocess.run([sys.executable, "-c", STALE, build, __file__], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        said.append(p.stdout.strip().splitlines()[-1])
    # ELSEWHERE: no other thread ever saw the metadata flag set
    assert said == ["RAN False TEXT False ELSEWHERE False",   # nothing to name
                    "RAN False TEXT True ELSEWHERE False",    # the stale entry ran; compiled once more
                    "RAN False TEXT True ELSEWHERE False"]
