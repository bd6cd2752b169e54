"""The passes of a step (``profiling.PASSES``): the two scopes the program writes by
hand (``pass:bootstrap`` in the train step's loss, ``pass:again`` around the chunk a
hand-written backward computes a second time), what AD writes beside them
(``rematted_computation``, ``transpose(``), the reader of an executable's text
(``hlo_passes``) against the benchmark's (``benchmark/pass_times.py``), and the text
of a fused program whose executable came from a cache entry written before the
passes were named.  A small checkpointed network on the real delta-rule walk, lowered
and compiled on the CPU."""
import collections
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from flax import linen as nn

from ape_x_dqn_tpu.learner.train_step import build_train_step, init_train_state, make_optimizer
from ape_x_dqn_tpu.models.dueling import DuelingOutput
from ape_x_dqn_tpu.ops.chunked_delta import chunked_delta
from ape_x_dqn_tpu.types import NStepTransition, PrioritizedBatch
from ape_x_dqn_tpu.utils import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "benchmark"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

B, T, D, H, K, A = 4, 12, 8, 2, 4, 3


class Layer(nn.Module):
    """One linear-attention layer on the program's own walk: its backward pass is
    written by hand and computes each chunk again."""

    @nn.compact
    def __call__(self, h):
        with profiling.part("mixer"):
            heads = lambda x: jnp.moveaxis(x.reshape(*x.shape[:2], H, -1), 2, 1)  # noqa: E731
            q, k, v = (heads(nn.Dense(H * K, name=n)(h)) for n in "qkv")
            g = -jax.nn.softplus(jnp.moveaxis(nn.Dense(H, name="g")(h), 2, 1))
            beta = jax.nn.sigmoid(jnp.moveaxis(nn.Dense(H, name="beta")(h), 2, 1))
            o = chunked_delta(q, k, v, g, beta, 4)
            return h + nn.Dense(D, name="out")(jnp.moveaxis(o, 1, 2).reshape(*h.shape[:2], -1))


class Net(nn.Module):
    """Two checkpointed layers and a dueling head; ``remat`` False keeps everything."""
    remat: bool = True

    @nn.compact
    def __call__(self, x):
        with profiling.part("stem"):
            h = x.astype(jnp.float32).reshape(x.shape[0], T, D) / 255.0
        layer = nn.remat(Layer) if self.remat else Layer
        for i in range(2):
            h = layer(name=f"layer_{i}")(h)
        with profiling.part("head"):
            pooled = jnp.mean(h, axis=1)
            value, adv = nn.Dense(1, name="value")(pooled), nn.Dense(A, name="adv")(pooled)
            return DuelingOutput(value, adv, value + adv - jnp.mean(adv, -1, keepdims=True))


def _step_text(net) -> str:
    x = (jnp.arange(B * T * D, dtype=jnp.int32) * 37 % 256).astype(jnp.uint8).reshape(B, T * D)
    opt = make_optimizer("rmsprop", learning_rate=1e-3)
    state = init_train_state(net, opt, jax.random.PRNGKey(0), x[:1])
    step = build_train_step(net, opt, sync_in_step=False, jit=False)
    batch = PrioritizedBatch(
        transition=NStepTransition(obs=x, action=jnp.arange(B) % A, reward=jnp.ones(B),
                                   discount=jnp.full((B,), 0.9), next_obs=x[::-1]),
        indices=jnp.arange(B), is_weights=jnp.ones(B))
    return jax.jit(step).lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def text():
    return _step_text(Net())


@pytest.fixture(scope="module")
def own(text):
    """{instruction: its own op_name} and ``hlo_passes`` of the compiled step."""
    return profiling._own_op_names(text), profiling.hlo_passes(text)


def test_pass_refuses_a_name_outside_its_scopes():
    with profiling.pass_("bootstrap"), profiling.pass_("again"):
        pass
    for name in ("recompute", "backward", "forward", "bootstraps"):
        with pytest.raises(ValueError, match="unknown pass scope"):
            profiling.pass_(name)
    assert profiling.PASSES == ("bootstrap", "forward", "recompute", "backward")
    assert profiling.PASS_PREFIX == "pass:" and profiling.PASS_PREFIX != profiling.PART_PREFIX


def test_jax_still_names_a_checkpoints_recomputation(text):
    assert f"/{profiling.REMAT_SEGMENT}/" in text, (
        f"jax {jax.__version__} no longer writes the name-stack segment "
        f"{profiling.REMAT_SEGMENT!r} around what a jax.checkpoint computes again "
        "(jax/_src/ad_checkpoint.py, transpose_jaxpr: extend_name_stack): change "
        "profiling.REMAT_SEGMENT and benchmark/pass_times._AGAIN to the new segment, or "
        "pass.recompute_step_us reads the hand-written backwards' share alone")
    assert f"/{profiling.REMAT_SEGMENT}/" not in _step_text(Net(remat=False))


@pytest.mark.parametrize("marks,want", [
    (("jit(train_step)/jvp(stage:forward)/pass:bootstrap/",), "bootstrap"),
    (("jit(train_step)/jvp(stage:forward)/Net/",), "forward"),
    (("transpose(jvp(stage:forward))", "/rematted_computation/"), "recompute"),
    (("transpose(jvp(stage:forward))", "/pass:again/"), "recompute"),
    (("transpose(jvp(stage:forward))", "/transpose(pass:again)/"), "backward"),
    (("jit(train_step)/transpose(jvp(stage:forward))/Net/torso:head/",), "backward"),
    (("transpose(jvp(stage:forward))/Net/jvp(stage:forward)/Net/checkpoint/layer_1/torso:mixer/k/",),
     "backward"),
], ids=["bootstrap", "forward", "checkpoint-again", "chunk-again", "chunk-pulled-back",
        "head-pulled-back", "layer-pulled-back"])
def test_every_class_of_instruction_lands_in_its_pass(own, marks, want):
    names, passes = own
    found = [n for n, op in names.items() if all(m in op for m in marks)]
    assert found, marks
    assert {passes[n] for n in found} == {want}


def test_the_step_holds_all_four_passes_and_nothing_else_has_one(own):
    names, passes = own
    count = collections.Counter(passes.values())
    assert set(count) == set(profiling.PASSES) and min(count.values()) > 20, count
    stages = {n: profiling._op_stage(op) for n, op in names.items()}
    assert all(stages[n] in ("forward", "backward") for n in passes)
    assert {n for n, s in stages.items() if s in ("forward", "backward")} == set(passes)
    assert any(s == "optimizer" for s in stages.values())
    # the walk is computed again twice a layer: by the block's checkpoint, forward with
    # its states kept, and chunk by chunk by the walk's own backward
    walk = [n for n in passes if "torso:delta_scan" in names[n]]
    by_pass = collections.Counter(passes[n] for n in walk)
    assert set(by_pass) == set(profiling.PASSES), by_pass
    again = [n for n in walk if "/pass:again/" in names[n]]
    assert again and all(profiling.REMAT_SEGMENT not in names[n] for n in again)
    # the bootstrap's forwards are not differentiated: none of them is under a pull-back
    assert not any("transpose(" in names[n] for n in passes if passes[n] == "bootstrap")


def test_a_network_that_keeps_everything_recomputes_only_the_walks_chunks():
    kept = _step_text(Net(remat=False))
    names, passes = profiling._own_op_names(kept), profiling.hlo_passes(kept)
    again = [n for n, p in passes.items() if p == "recompute"]
    assert again and all("/pass:again/" in names[n] for n in again)


def test_the_benchmarks_reader_gives_the_same_answer_on_every_scoped_instruction(text, own):
    import pass_times
    import stage_times

    _names, theirs = own
    stages, _ = stage_times.instruction_stages(text)
    mine, _mixed = pass_times.instruction_passes(text, stages)
    assert len(theirs) > 200 and set(theirs) <= set(mine)
    assert [n for n in theirs if mine[n] != theirs[n]] == []
    handed_on = set(mine) - set(theirs)     # the compiler's unscoped copies and slices
    assert all(profiling.hlo_stages(text)[n] == profiling.OTHER for n in handed_on)
    assert all(pass_times.scope_pass(op) == theirs.get(n) for n, op in _names.items())


# ------------------------------------------------------- the operator's summary

_F, _B = "jit(toy)/jvp(stage:forward)", "jit(toy)/transpose(jvp(stage:forward))"
SUMMARY_HLO = f"""HloModule jit_toy

ENTRY %main (x: f32[4]) -> f32[4] {{
  %x = f32[4]{{0}} parameter(0)
  %boot.1 = f32[4]{{0}} fusion(%x), kind=kLoop, calls=%f, metadata={{op_name="{_F}/pass:bootstrap/Net/torso:mixer/dot_general"}}
  %fwd.2 = f32[4]{{0}} fusion(%x), kind=kLoop, calls=%f, metadata={{op_name="{_F}/Net/torso:mixer/dot_general"}}
  %copy.3 = f32[4]{{0}} copy(%fwd.2)
  %remat.4 = f32[4]{{0}} fusion(%copy.3), kind=kLoop, calls=%f, metadata={{op_name="{_B}/Net/checkpoint/rematted_computation/layer_0/torso:mixer/dot_general"}}
  %again.5 = f32[4]{{0}} fusion(%remat.4), kind=kLoop, calls=%f, metadata={{op_name="{_B}/Net/checkpoint/layer_0/torso:delta_scan/while/body/pass:again/jvp(scalar_gate)/mul"}}
  %pull.6 = f32[4]{{0}} fusion(%again.5), kind=kLoop, calls=%f, metadata={{op_name="{_B}/Net/checkpoint/layer_0/torso:delta_scan/while/body/transpose(pass:again)/jvp(scalar_gate)/mul"}}
  ROOT %opt.7 = f32[4]{{0}} fusion(%pull.6, %boot.1), kind=kLoop, calls=%f, metadata={{op_name="jit(toy)/stage:optimizer/add"}}
}}
"""


def test_summarize_trace_gives_seconds_per_pass(tmp_path, monkeypatch):
    """``/varz?trace=1`` and ``--profile-dir`` show an operator the split the benchmark
    reads: ``pass_s`` beside ``stage_s`` and ``part_s``, an instruction's own pass."""
    from jax.profiler import ProfileData

    from tests.test_stage_scopes import _fake_profile

    durations = [("boot.1", 40), ("fwd.2", 20), ("copy.3", 5), ("remat.4", 18), ("again.5", 6),
                 ("pull.6", 9), ("opt.7", 3)]
    ops, at = [], 0
    for name, us in durations:
        ops.append((f"%{name} = f32[4]{{0}} fusion(%x)", at, us))
        at += us
    monkeypatch.setattr(ProfileData, "from_file", staticmethod(
        lambda path: _fake_profile(ops, [("jit_toy(1)", 0, at)], [])))
    d = tmp_path / "log" / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    prog = profiling._FusedProgram(lambda: None, {})
    prog.signature, prog.text = (), SUMMARY_HLO
    monkeypatch.setattr(profiling, "_fused_programs", {"jit_toy": [prog]})
    s = profiling.summarize_trace(str(tmp_path / "log"))
    us = lambda table: {k: round(v * 1e6, 3) for k, v in table.items()}  # noqa: E731
    assert us(s["pass_s"]) == {"bootstrap": 40.0, "forward": 20.0, "recompute": 24.0, "backward": 9.0}
    # the passes divide forward and backward; the unscoped copy is `other` to this reader
    assert us(s["stage_s"]) == {"forward": 60.0, "backward": 33.0, "optimizer": 3.0, "other": 5.0}
    assert us(s["part_s"]) == {"mixer": 78.0, "delta_scan": 15.0}


# ------------------------------------------------- a text from a stale cache entry

class _Stub:
    """What ``jit.lower(...).compile().as_text()`` answers from a cache entry."""

    def __init__(self, text):
        self.text, self.asked = text, 0

    def lower(self, *_args):
        self.asked += 1
        return self

    compile = lambda self: self  # noqa: E731
    as_text = lambda self: self.text  # noqa: E731


def _scoped(x):
    with profiling.stage("forward"):
        with profiling.pass_("bootstrap"):
            y = jnp.sin(x)
        return jnp.cos(x) + y


@pytest.mark.parametrize("lacks,again", [
    ((), False), (("pass:",), True), (("stage:",), True), (("stage:", "pass:"), True),
], ids=["both-named", "written-before-the-passes", "no-stage", "written-before-the-scopes"])
def test_hlo_text_compiles_again_when_the_cached_text_lacks_a_prefix(lacks, again):
    """The persistent cache's key leaves metadata out: the parent's entry answers the
    change's compile with the parent's names.  Either prefix missing is such a load."""
    prog = profiling._FusedProgram(_scoped, {})
    prog.signature = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    real = prog.jitted.lower(*prog.signature).compile().as_text()
    assert "stage:forward/pass:bootstrap" in real
    stale = real
    for prefix in lacks:
        stale = stale.replace(prefix, "old_")
    prog.jitted = stub = _Stub(stale)
    got = prog.hlo_text()
    assert stub.asked == 1
    if again:
        assert got is not stale and "stage:forward/pass:bootstrap" in got
    else:
        assert got is stale
    assert prog.hlo_text() is got and stub.asked == 1     # kept, not made again


STALE = r"""
import contextlib, os, sys
import jax
from ape_x_dqn_tpu.utils import profiling
from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache()
if sys.argv[1] == "parent":   # what the commit before the passes compiled
    profiling.pass_ = lambda name: contextlib.nullcontext()
sys.path.insert(0, os.path.dirname(sys.argv[2]))
import test_stage_scopes as t
fused, name, _ = t._call_dedup(True)
ran = fused.lower(*profiling._fused_programs[name][-1].signature).compile().as_text()
text = profiling.fused_hlo_text(name)
# the instructions alone: past the header's tables of files and frames, less the metadata
strip = lambda s: __import__("re").sub(
    r", metadata=\{[^}]*\}", "", s[s.index("\n\n", s.index("\nStackFrames\n")):])
print("RAN", "stage:" in ran, "pass:" in ran, "TEXT", "stage:" in text, "pass:" in text,
      "SAME", strip(ran) == strip(text), "PASSES", sorted(set(profiling.hlo_passes(text).values())))
"""


def test_the_parents_cache_entry_still_gives_the_change_a_text_with_the_passes(tmp_path):
    """Parent first, change after, one cache directory, as the driver measures them: the
    change's process loads the executable the parent wrote, whose text names stages
    and no pass; ``hlo_text`` compiles under the key with the metadata and names them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    said = []
    for build in ("parent", "change", "change"):
        p = subprocess.run([sys.executable, "-c", STALE, build,
                            os.path.join(ROOT, "tests", "test_stage_scopes.py")],
                           env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        said.append(p.stdout.strip().splitlines()[-1])
    three = "PASSES ['backward', 'bootstrap', 'forward']"      # an MLP recomputes nothing
    assert said == [
        "RAN True False TEXT True False SAME True PASSES ['backward', 'forward']",
        f"RAN True False TEXT True True SAME True {three}",    # the parent's entry ran
        f"RAN True False TEXT True True SAME True {three}"], said
