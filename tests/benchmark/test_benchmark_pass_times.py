"""pass_times: from the program's HLO text and a device trace to device time per
pass of the step (bootstrap, forward, recompute, backward) and the recurrent
walks' share of two of them.  Hand-made text and events; then the pair recorded on
a TPU v5e by PR 26, a program that names no pass, on which every reader is silent
and the accepted tables read what they read; then the manifest's six entries."""
import json
import os
import subprocess
import types

import pytest

import manifest as mf
import parts_times
import pass_times as pt
import stage_times as st
import trace_reduce as tr
from trace_reduce import DeviceTrace, Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SIX = ("pass.bootstrap_step_us", "pass.forward_step_us", "pass.recompute_step_us",
       "pass.backward_step_us", "pass.walk_recompute_step_us",
       "pass.walk_backward_step_us")
PARENT = "14089a4e9e2fcc59be1d87440a57c8002134b4c8"

_F = "jit(fused)/while/body/closed_call/jvp(stage:forward)"
_B = "jit(fused)/while/body/closed_call/transpose(jvp(stage:forward))"
_WALK = "layer_0/torso:mixer/linear_attention/torso:delta_scan/torso:delta_scan"
HLO = f"""HloModule jit_fused, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  ROOT %neg.1 = f32[4]{{0}} negate(%p), metadata={{op_name="jit(fused)/stage:sample/neg"}}
}}

%fused_computation.2 (p.2: f32[4], w.2: f32[4]) -> f32[4] {{
  %p.2 = f32[4]{{0}} parameter(0)
  %w.2 = f32[4]{{0}} parameter(1)
  %dot.1 = f32[4]{{0}} multiply(%p.2, %w.2), metadata={{op_name="{_B}/Net/layer_1/torso:dense_ffn/dot_general"}}
  ROOT %upd.1 = f32[4]{{0}} subtract(%w.2, %dot.1), metadata={{op_name="jit(fused)/while/body/closed_call/stage:optimizer/sub"}}
}}

%fused_computation.3 (p.3: f32[4], w.3: f32[4]) -> f32[4] {{
  %p.3 = f32[4]{{0}} parameter(0)
  %w.3 = f32[4]{{0}} parameter(1)
  %re.1 = f32[4]{{0}} multiply(%p.3, %w.3), metadata={{op_name="{_B}/Net/checkpoint/rematted_computation/layer_1/torso:dense_ffn/mul"}}
  ROOT %bw.1 = f32[4]{{0}} subtract(%w.3, %re.1), metadata={{op_name="{_B}/Net/checkpoint/layer_1/torso:dense_ffn/mul"}}
}}

%walk_back.4 (c: (s32[], f32[4])) -> (s32[], f32[4]) {{
  %c = (s32[], f32[4]{{0}}) parameter(0)
  %s = f32[4]{{0}} get-tuple-element(%c), index=1
  %again.40 = f32[4]{{0}} fusion(%s), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_B}/Net/checkpoint/{_WALK}/while/body/closed_call/pass:again/jvp(scalar_gate)/dot_general"}}
  %copy.41 = f32[4]{{0}} copy(%again.40)
  %pull.42 = f32[4]{{0}} fusion(%copy.41), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_B}/Net/checkpoint/{_WALK}/while/body/closed_call/transpose(pass:again)/jvp(scalar_gate)/dot_general"}}
  %j = s32[] get-tuple-element(%c), index=0
  ROOT %o = (s32[], f32[4]{{0}}) tuple(%j, %pull.42)
}}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {{
  %t = (s32[], f32[4]{{0}}) parameter(0)
  %x = f32[4]{{0}} get-tuple-element(%t), index=1
  %slice.20 = f32[4]{{0}} dynamic-slice(%x), metadata={{op_name="jit(fused)/while/body/dynamic_slice"}}
  %boot.21 = f32[4]{{0}} fusion(%slice.20), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}/pass:bootstrap/Net/checkpoint/layer_1/torso:dense_ffn/dot_general"}}
  %bootwalk.22 = f32[4]{{0}} fusion(%boot.21), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}/pass:bootstrap/Net/checkpoint/{_WALK}/while/body/closed_call/scalar_gate/dot_general"}}
  %fwd.23 = f32[4]{{0}} fusion(%slice.20), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}/Net/checkpoint/{_WALK}/while/body/closed_call/scalar_gate/dot_general"}}
  %copy.24 = f32[4]{{0}} copy(%fwd.23)
  %loss.25 = f32[4]{{0}} fusion(%copy.24, %bootwalk.22), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}/sub"}}
  %remat.26 = f32[4]{{0}} fusion(%copy.24), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_B}/Net/checkpoint/rematted_computation/{_WALK}/while/body/closed_call/scalar_gate/dot_general"}}
  %nested.27 = f32[4]{{0}} fusion(%remat.26), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_B}/Net/checkpoint/rematted_computation/layer_0/torso:mixer/linear_attention/checkpoint/rematted_computation/mul"}}
  %init.28 = (s32[], f32[4]{{0}}) tuple(%x, %nested.27)
  %while.29 = (s32[], f32[4]{{0}}) while(%init.28), condition=%cond.4, body=%walk_back.4, metadata={{op_name="{_B}/Net/checkpoint/{_WALK}/while"}}
  %res.30 = f32[4]{{0}} get-tuple-element(%while.29), index=1
  %attn_dkv.31 = f32[4]{{0}} custom-call(%res.30), custom_call_target="tpu_custom_call", metadata={{op_name="{_B}/Net/checkpoint/layer_3/torso:mixer/full_attention/torso:attn_full/attn_dkv"}}
  %mixed.32 = f32[4]{{0}} fusion(%attn_dkv.31, %x), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{_B}/Net/checkpoint/rematted_computation/layer_1/torso:dense_ffn/mul"}}
  %wgrad.33 = f32[4]{{0}} fusion(%mixed.32, %x), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{_B}/Net/layer_1/torso:dense_ffn/dot_general"}}
  %all-reduce.34 = f32[4]{{0}} all-reduce(%wgrad.33), to_apply=%sum, metadata={{op_name="jit(fused)/shard_map/while/body/closed_call/transpose(jvp(stage:forward))/psum_invariant"}}
  %opt.35 = f32[4]{{0}} fusion(%all-reduce.34, %x), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(fused)/while/body/closed_call/stage:optimizer/add"}}
  %prio.36 = f32[4]{{0}} fusion(%loss.25), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(fused)/while/body/closed_call/stage:restamp/abs"}}
  %i = s32[] get-tuple-element(%t), index=0
  ROOT %out = (s32[], f32[4]{{0}}) tuple(%i, %opt.35)
}}

ENTRY %main.3 (ring: f32[4]) -> f32[4] {{
  %ring = f32[4]{{0}} parameter(0), metadata={{op_name="replay_state.frames"}}
  %fusion.9 = f32[4]{{0}} fusion(%ring), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(fused)/stage:gather/gather"}}
  %zero = s32[] constant(0)
  %init = (s32[], f32[4]{{0}}) tuple(%zero, %fusion.9)
  %while.13 = (s32[], f32[4]{{0}}) while(%init), condition=%cond.4, body=%body.2, metadata={{op_name="jit(fused)/while"}}
  ROOT %res = f32[4]{{0}} get-tuple-element(%while.13), index=1
}}
"""
# what each instruction of the step is, and its microseconds in the trace below
STEP = [("slice.20", 5, "forward"), ("boot.21", 40, "bootstrap"), ("bootwalk.22", 30, "bootstrap"),
        ("fwd.23", 35, "forward"), ("copy.24", 3, "forward"), ("loss.25", 2, "forward"),
        ("remat.26", 33, "recompute"), ("nested.27", 7, "recompute"),
        ("attn_dkv.31", 21, "backward"), ("mixed.32", 11, "recompute"), ("wgrad.33", 50, "backward"),
        ("all-reduce.34", 9, "backward"), ("opt.35", 13, None), ("prio.36", 1, None)]
WALK_BACK = [("again.40", 6, "recompute"), ("copy.41", 1, "backward"), ("pull.42", 8, "backward")]
WALK_TRIPS, WALK_OWN = 3, 2   # the backward walk's loop: three trips, 2 us of its own


def test_scope_pass_reads_the_scopes_and_ads_marks():
    assert pt.scope_pass(f"{_F}/pass:bootstrap/Net/Conv_0/conv_general_dilated") == "bootstrap"
    assert pt.scope_pass(f"{_F}/Net/Conv_0/conv_general_dilated") == "forward"
    assert pt.scope_pass(f"{_B}/Net/Conv_0/conv_general_dilated") == "backward"
    assert pt.scope_pass(f"{_B}/Net/checkpoint/rematted_computation/layer_0/mul") == "recompute"
    # a mixer's checkpoint inside the block's: recompute, once
    assert pt.scope_pass(f"{_B}/Net/checkpoint/rematted_computation/layer_0/checkpoint/"
                         "rematted_computation/mul") == "recompute"
    assert pt.scope_pass(f"{_B}/Net/checkpoint/layer_0/while/body/pass:again/jvp(scalar_gate)/mul") == "recompute"
    # the pull-back of what ran under the scope is the backward pass proper
    assert pt.scope_pass(f"{_B}/Net/checkpoint/layer_0/while/body/transpose(pass:again)/jvp(scalar_gate)/mul") == "backward"
    # a forward inside a checkpoint is not a recomputation; no stage, or another, is no pass
    assert pt.scope_pass(f"{_F}/Net/checkpoint/layer_0/mul") == "forward"
    assert pt.scope_pass("jit(fused)/while/body/closed_call/stage:optimizer/add") is None
    assert pt.scope_pass("checkpoint/rematted_computation/layer_0/reduce_sum") is None


def test_instruction_passes_own_scope_inheritance_and_the_stages_bound():
    stages, _ = st.instruction_stages(HLO)
    passes, mixed = pt.instruction_passes(HLO, stages)
    for name, _us, want in STEP + WALK_BACK:
        assert passes.get(name) == want, name
    # consumed by the bootstrap and the differentiated forward: the first pass of its stage;
    # the forward's copy is read by the loss and, kept, by the recomputation: forward
    assert stages["slice.20"] == stages["copy.24"] == "forward"
    # inside the walk's body, consumed by the pull-back alone
    assert stages["copy.41"] == "backward"
    # a pass refines a stage: nothing outside forward and backward has one
    assert all(stages[n] in ("forward", "backward") for n in passes)
    assert {n for n in stages if stages[n] in ("forward", "backward")} == set(passes)
    assert "fusion.9" not in passes and "while.13" not in passes
    # the fusion that holds a recomputation and its pull-back is credited to the pass its
    # own metadata names; the weight gradient with the optimizer's update holds one pass
    assert mixed == {"mixed.32": {"recompute", "backward"}} and passes["mixed.32"] == "recompute"


@pytest.mark.parametrize("text", [HLO, None], ids=["hand-made", "recorded"])
def test_the_programs_own_summary_reads_the_same_passes(text):
    """``profiling.hlo_passes`` stops at an instruction's own scope, this reader hands
    the unscoped ones on: on every instruction with a pass of its own they agree."""
    from ape_x_dqn_tpu.utils import profiling

    if text is None:     # recorded before the passes were named: forward and backward alone
        text = open(os.path.join(DATA, "small_stage.hlo.txt")).read()
    stages, _ = st.instruction_stages(text)
    mine, _mixed = pt.instruction_passes(text, stages)
    theirs = profiling.hlo_passes(text)
    assert len(theirs) > 10 and set(theirs) <= set(mine)
    assert all(mine[n] == theirs[n] for n in theirs)
    assert set(theirs.values()) <= set(profiling.PASSES) == set(pt.PASSES)
    handed_on = set(mine) - set(theirs)
    assert handed_on and all(profiling.hlo_stages(text)[n] == profiling.OTHER for n in handed_on)
    assert pt.PREFIX == profiling.PASS_PREFIX and set(pt.WALKS) <= set(profiling.PARTS)


def _trace(chips=1):
    """Two whole runs of jit_fused, K = 2 steps each, a run cut by the window's start,
    and an ingest program between the whole ones."""
    us = 1e-6
    walk_us = WALK_OWN + WALK_TRIPS * sum(d for _n, d, _p in WALK_BACK)
    step_us = sum(d for _n, d, _p in STEP) + walk_us

    def run(t0):
        ops, at = [("fusion.9", 0, 50), ("while.13", 50, 2 * step_us + 10)], 55
        for _step in (0, 1):
            for name, d, _p in STEP:
                if name == "attn_dkv.31":      # the backward walk runs before it
                    ops.append(("while.29", at, walk_us))
                    inner = at + WALK_OWN
                    for _trip in range(WALK_TRIPS):
                        for n, dd, _pp in WALK_BACK:
                            ops.append((n, inner, dd))
                            inner += dd
                    at += walk_us
                ops.append((name, at, d))
                at += d
        return [Event(f"%{n} = f32[4]{{0}} fusion(%x)", (t0 + s) * us, (t0 + s + d) * us)
                for n, s, d in ops]

    length = 60 + 2 * step_us + 30
    starts = (-length / 2, 1000, 1000 + length + 200)
    dev = DeviceTrace(
        ops=[e for t0 in starts for e in run(t0)]
        + [Event("%add.1 = s32[] add(%a, %b)", (1000 + length + 50) * us, (1000 + length + 130) * us)],
        async_ops=[],
        modules=[Event("jit_fused(123)", t0 * us, (t0 + length) * us) for t0 in starts]
        + [Event("jit_add_frames(9)", (1000 + length + 50) * us, (1000 + length + 130) * us)])
    spans = [Event("bench:force", 0.0, 10 * us),
             Event("bench:force", 3000 * us, (3000 + 2 * length) * us)]
    return Trace({f"/device:TPU:{i}": dev for i in range(chips)}, spans)


def _readings(chips=1):
    return types.SimpleNamespace(
        trace=_trace(chips), fused_program="jit_fused", trace_reduce=tr,
        config={"steps_per_call": 2, "parts": ["delta_scan", "dense_ffn"],
                "parts_scope": "torso:delta_scan"})


def _reader(name):
    return mf.load_module(os.path.join(mf.HERE, "layer_metrics", name + ".py"),
                          "m_" + name.replace(".", "_")).read


@pytest.mark.parametrize("chips", [1, 4])
def test_the_four_passes_add_up_to_forward_and_backward_and_the_walks_obey_their_bounds(
        chips, monkeypatch, capsys):
    monkeypatch.setattr(st, "program_texts", lambda name: iter(["HloModule unrelated\n", HLO]))
    r = _readings(chips)
    got = {name: _reader(name)(r) for name in SIX}
    by_pass = {p: sum(d for _n, d, q in STEP if q == p)
               + WALK_TRIPS * sum(d for _n, d, q in WALK_BACK if q == p) for p in pt.PASSES}
    by_pass["backward"] += WALK_OWN     # the walk's own time: its ``while`` is scoped
    assert by_pass == {"bootstrap": 70, "forward": 45, "recompute": 69, "backward": 109}
    assert [got[n] for n in SIX[:4]] == pytest.approx([by_pass[p] for p in pt.PASSES], rel=1e-12)
    # the sum is the two accepted stage metrics' of the same run
    stages_sum = st.read(r, "forward") + st.read(r, "backward")
    assert sum(got[n] for n in SIX[:4]) == pytest.approx(stages_sum, rel=1e-12)
    assert got[SIX[0]] + got[SIX[1]] == pytest.approx(st.read(r, "forward"), rel=1e-12)
    # the walks: the block's recomputation of the scan and the chunk computed again; the
    # pull-back, its unscoped copy and the loop's own time.  Not the bootstrap's walk,
    # not the forward's, not the nested recomputation outside the scan's scope
    assert got[SIX[4]] == pytest.approx(33 + 3 * 6) and got[SIX[5]] == pytest.approx(3 * 9 + 2)
    scan_us = parts_times.read(r, "delta_scan")
    assert scan_us == pytest.approx(30 + 35 + 3 + 33 + WALK_OWN + WALK_TRIPS * 15)
    assert got[SIX[4]] + got[SIX[5]] <= scan_us
    assert got[SIX[4]] <= got[SIX[2]] and got[SIX[5]] <= got[SIX[3]]
    said = capsys.readouterr().out
    share = f"{11 / 293 * 100:.2f}%"     # mixed.32 alone, and its passes divide one stage
    assert f"{share} of that in fusions that hold more than one pass, {share} in fusions" in said
    assert f"(credited to bootstrap 0.00%, forward 0.00%, recompute {share}, backward 0.00%)" in said


def test_a_network_that_recomputes_and_walks_nothing_reads_zero_not_nothing(monkeypatch):
    """The conv cells: the program names ``pass:bootstrap``, so all six give a number."""
    plain = "\n".join(line for line in HLO.splitlines()
                      if "rematted_computation" not in line and "pass:again" not in line)
    plain = plain.replace("torso:delta_scan", "torso:stem")
    monkeypatch.setattr(st, "program_texts", lambda name: iter([plain]))
    r = _readings()
    got = {name: _reader(name)(r) for name in SIX}
    assert all(v is not None for v in got.values())
    assert got[SIX[2]] == got[SIX[4]] == got[SIX[5]] == 0.0
    assert got[SIX[0]] == pytest.approx(70) and got[SIX[3]] > 0
    assert sum(got[n] for n in SIX[:4]) == pytest.approx(
        st.read(r, "forward") + st.read(r, "backward"), rel=1e-12)


def test_a_program_that_names_no_pass_gives_none_of_the_six(monkeypatch):
    """The parent of the PR that added the scopes: ``stage:`` and ``torso:`` and no
    ``pass:``.  Every accepted reader reads what it read, the six say nothing."""
    unnamed = HLO.replace("pass:bootstrap/", "").replace("transpose(pass:again)/", "").replace(
        "pass:again/", "")
    assert "pass:" not in unnamed and "rematted_computation" in unnamed
    r = _readings()
    monkeypatch.setattr(st, "program_texts", lambda name: iter([unnamed]))
    assert all(_reader(name)(r) is None for name in SIX) and pt.table(r) is None
    assert st.read(r, "forward") == pytest.approx(115) and st.read(r, "backward") == pytest.approx(178)
    assert parts_times.read(r, "delta_scan") == pytest.approx(148)
    # no text at all, no fused_hlo_texts in the program, a trace of one whole run: nothing
    for texts, trace in (([], r.trace), ([HLO], Trace(r.trace.devices, r.trace.spans[:1] + [
            Event("bench:force", 0.0011, 0.0013)]))):
        quiet = types.SimpleNamespace(**{**vars(_readings()), "trace": trace})
        monkeypatch.setattr(st, "program_texts", lambda name, texts=texts: iter(texts))
        assert pt.table(quiet) is None and _reader(SIX[2])(quiet) is None


def test_on_the_recorded_tpu_pair_the_six_are_silent_and_the_stage_table_is_what_it_was(monkeypatch):
    """PR 26's recording of the toy dedup cell on a TPU v5e: its text names stages and
    no pass."""
    trace = tr.load(os.path.join(DATA, "small_stage.xplane.pb"))
    text = open(os.path.join(DATA, "small_stage.hlo.txt")).read()
    assert "stage:" in text and "pass:" not in text
    monkeypatch.setattr(st, "program_texts", lambda name: [text] if name == "jit_fused" else [])
    r = types.SimpleNamespace(trace=trace, fused_program="jit_fused", trace_reduce=tr,
                              config={"steps_per_call": 4})
    assert all(_reader(name)(r) is None for name in SIX)
    assert st.table(r) == pytest.approx({
        "ingest": 52.9875, "sample": 4.62725, "gather": 47.88275, "restamp": 0.838375,
        "forward": 24.03125, "backward": 12.634, "optimizer": 3.215875,
        "target_sync": 0.116625, "other": 11.19725}, rel=1e-4)
    # with the passes named by the text alone (the scope put where the program now puts it)
    # the same trace splits: the sums are the table's
    named = text.replace("jvp(stage:forward)/DuelingMLP", "jvp(stage:forward)/pass:bootstrap/DuelingMLP", 1)
    if named != text:
        monkeypatch.setattr(st, "program_texts", lambda name: [named])
        del r._pass_table
        t = pt.table(r)
        assert sum(t[p] for p in pt.PASSES) == pytest.approx(24.03125 + 12.634, rel=1e-4)
        assert t["recompute"] == t["walk_recompute"] == t["walk_backward"] == 0.0


def _appended_only(before: dict, now: dict) -> list:
    """What of ``before`` is not in ``now`` as it was: scalars equal, every list a
    prefix, a metric's ``workloads`` a prefix of its list now."""
    wrong = [k for k in before if not isinstance(before[k], list) and before[k] != now.get(k)]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for i, old in enumerate(before[group]):
            new = now[group][i] if i < len(now[group]) else {}
            cut = dict(new)
            if "workloads" in old and "workloads" in new:
                cut["workloads"] = new["workloads"][:len(old["workloads"])]
            if cut != old:
                wrong.append(f"{group}[{i}] {old.get('name')}")
    return wrong + [k for k in ("command", "paths") if before[k] != now[k]]


def test_the_six_entries_are_appended_and_have_readers_and_no_list():
    m = mf.load_manifest()
    names = [x["name"] for x in m["per_layer"]]
    at = names.index(SIX[0])
    assert tuple(names[at:at + 6]) == SIX and names.index("gdn.mfu_pct") == at - 1
    for x in m["per_layer"][at:at + 6]:
        assert x == {"name": x["name"], "unit": "us", "better": "lower", "source": "device_trace",
                     "layer": "learner", "moves": "learn_samples_per_s"}       # no ``workloads``
        assert os.path.isfile(os.path.join(mf.HERE, "layer_metrics", x["name"] + ".py"))
    # so every cell that reports the rate reports them, the ones later PRs add too
    for w in m["workloads"]:
        assert set(SIX) <= {x["name"] for x in mf.Cell(m, w["name"]).per_layer()}
    # against the parent's file where git has it (a checkout of committed files alone has not)
    try:
        before = json.loads(subprocess.run(
            ["git", "show", f"{PARENT}:BENCHMARK.json"], cwd=mf.ROOT, check=True,
            capture_output=True, timeout=60).stdout)
    except (OSError, subprocess.SubprocessError):
        before = None
    if before is not None:
        assert _appended_only(before, m) == []
        assert len(m["per_layer"]) >= len(before["per_layer"]) + 6
    tampered = json.loads(json.dumps(m))
    tampered["per_layer"][0]["unit"] = "s"
    tampered["per_layer"][2]["workloads"].pop(0)
    assert _appended_only(m, tampered) == ["per_layer[0] ingest.ms_per_call", "per_layer[2] fused_roofline"]
