"""stage_times: from the program's HLO text and a device trace to device time per
stage.  Hand-made text and events; then one pair recorded on a TPU v5e (PR 26): the toy
dedup cell of test_benchmark_toy_cell (K=4, batch 8, three traced calls, two in
flight) and the text ``profiling.fused_hlo_text`` gave for its fused program."""
import os
import types

import pytest

import stage_times as st
import trace_reduce as tr
from trace_reduce import DeviceTrace, Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = """HloModule jit_fused, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(fused)/stage:sample/neg"}
}

%fused_computation.2 (p.2: f32[4], w.2: f32[4]) -> f32[4] {
  %p.2 = f32[4]{0} parameter(0)
  %w.2 = f32[4]{0} parameter(1)
  %dot.1 = f32[4]{0} multiply(%p.2, %w.2), metadata={op_name="jit(fused)/while/body/closed_call/transpose(jvp(stage:forward))/dot_general"}
  ROOT %upd.1 = f32[4]{0} subtract(%w.2, %dot.1), metadata={op_name="jit(fused)/while/body/closed_call/stage:optimizer/sub"}
}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%t), index=1
  %slice.20 = f32[4]{0} dynamic-slice(%x), metadata={op_name="jit(fused)/while/body/dynamic_slice"}
  %fusion.21 = f32[4]{0} fusion(%slice.20), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/closed_call/jvp(stage:forward)/conv_general_dilated"}
  %fusion.22 = f32[4]{0} fusion(%fusion.21, %x), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(fused)/while/body/closed_call/transpose(jvp(stage:forward))/conv_general_dilated"}
  %all-reduce.23 = f32[4]{0} all-reduce(%fusion.22), to_apply=%sum, metadata={op_name="jit(fused)/shard_map/while/body/closed_call/transpose(jvp(stage:forward))/psum_invariant"}
  %fusion.24 = f32[4]{0} fusion(%all-reduce.23, %x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/closed_call/stage:optimizer/add"}
  %fusion.25 = f32[4]{0} fusion(%fusion.21), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/closed_call/stage:restamp/abs"}
  %mean.26 = f32[4]{0} multiply(%fusion.21, %fusion.21), metadata={op_name="jit(fused)/while/body/closed_call/div"}
  %i = s32[] get-tuple-element(%t), index=0
  ROOT %out = (s32[], f32[4]{0}) tuple(%i, %fusion.24)
}

ENTRY %main.3 (ring: f32[4], chunk: f32[4]) -> f32[4] {
  %ring = f32[4]{0} parameter(0), metadata={op_name="replay_state.frames"}
  %chunk = f32[4]{0} parameter(1), metadata={op_name="chunk"}
  %fusion.5 = f32[4]{0} fusion(%ring, %chunk), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/stage:ingest/scatter"}
  %copy.6 = f32[4]{0:T(4)} copy(%fusion.5), metadata={op_name="replay_state.frames"}
  %copy-start.7 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%copy.6)
  %copy-done.8 = f32[4]{0} copy-done(%copy-start.7)
  %fusion.9 = f32[4]{0} fusion(%copy-done.8), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/stage:gather/gather"}
  %copy.10 = f32[4]{0} copy(%fusion.5)
  %fusion.11 = f32[4]{0} fusion(%copy.10), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/stage:sample/cumsum"}
  %fusion.12 = f32[4]{0} fusion(%copy.10), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/stage:restamp/scatter"}
  %zero = s32[] constant(0)
  %copy.17 = f32[4]{0} copy(%fusion.9)
  %init = (s32[], f32[4]{0}) tuple(%zero, %copy.17)
  %while.13 = (s32[], f32[4]{0}) while(%init), condition=%cond.4, body=%body.2, metadata={op_name="jit(fused)/while"}
  %res = f32[4]{0} get-tuple-element(%while.13), index=1
  %fusion.14 = f32[4]{0} fusion(%res), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/stage:target_sync/select_n"}
  %fusion.15 = f32[4]{0} fusion(%res, %fusion.12), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/stage:restamp/scatter"}
  %copy.16 = f32[4]{0} copy(%fusion.9)
  ROOT %outs = (f32[4]{0}, f32[4]{0}, f32[4]{0}) tuple(%fusion.14, %fusion.15, %copy.16)
}
"""


def test_scope_stage_reads_the_innermost_scope_and_the_transpose():
    assert st.scope_stage("jit(fused)/stage:sample/jit(searchsorted)/while/body/eq") == "sample"
    assert st.scope_stage("jit(fused)/while/body/closed_call/jvp(stage:forward)/Conv_0/dot") == "forward"
    assert st.scope_stage(
        "jit(body)/shard_map/while/body/closed_call/transpose(jvp(stage:forward))/psum") == "backward"
    assert st.scope_stage("jit(fused)/while/body/closed_call/stage:restamp/abs") == "restamp"
    assert st.scope_stage("jit(fused)/stage:forward/x/stage:gather/y") == "gather"
    assert st.scope_stage("jit(fused)/while/body/dynamic_slice") is None


def test_instruction_stages_nesting_inheritance_and_disagreement():
    s, mixed = st.instruction_stages(HLO)
    # scoped, under the while's body and outside it
    assert [s[k] for k in ("fusion.21", "fusion.22", "all-reduce.23", "fusion.24", "fusion.25")] == [
        "forward", "backward", "backward", "optimizer", "restamp"]
    assert s["fusion.5"] == "ingest" and s["fusion.14"] == "target_sync" and s["neg.1"] == "sample"
    # the ring's layout copy and its copy-start/done carry no scope: they are the
    # gather's, which alone consumes them
    assert [s[k] for k in ("copy.6", "copy-start.7", "copy-done.8")] == ["gather"] * 3
    # the scan's slice feeds the forward pass and, through it, the backward: forward
    assert s["slice.20"] == "forward"
    # no consumer with a stage: the producers' (a metric from forward values, the
    # gathered batch re-laid-out for the scan)
    assert s["mean.26"] == "forward" and s["copy.16"] == "gather"
    # what goes into the scan is not consumed by what comes out of it: the loop hands
    # nothing on, so the gathered batches' copy is the gather's, not restamp's or the sync's
    assert s["copy.17"] == "gather"
    # consumed by two stages that are not forward and backward: other
    assert s["copy.10"] == st.OTHER and s["while.13"] == st.OTHER
    # a fusion that holds two stages is credited to the one its metadata names
    assert s["fusion.22"] == "backward" and mixed == {"fusion.22"}


def _trace(chips=1):
    """Two whole runs of jit_fused (each 1,000 us: 100 ingest, 300 copy, 50 gather, a
    500 us while of two steps, 20 target sync, 30 idle), one run cut by the window's
    start, and an ingest program of 80 us between them; K = 2."""
    us = 1e-6

    def run(t0):
        ops = [("fusion.5", 0, 100), ("copy.6", 100, 300), ("fusion.9", 400, 50),
               ("while.13", 450, 500), ("fusion.14", 950, 20)]
        for step in (0, 1):
            b = 455 + 245 * step
            ops += [("slice.20", b, 5), ("fusion.21", b + 5, 60), ("fusion.22", b + 65, 100),
                    ("all-reduce.23", b + 165, 20), ("fusion.24", b + 185, 40),
                    ("fusion.25", b + 225, 5), ("fusion.777", b + 230, 10)]
        return [Event(f"%{n} = f32[4]{{0}} fusion(%x)", (t0 + s) * us, (t0 + s + d) * us)
                for n, s, d in ops]

    starts = (-500, 1000, 2200)
    dev = DeviceTrace(
        ops=[e for t0 in starts for e in run(t0)]
        + [Event("%add.1 = s32[] add(%a, %b)", 2050 * us, 2130 * us)],
        async_ops=[],
        modules=[Event("jit_fused(123)", t0 * us, (t0 + 1000) * us) for t0 in starts]
        + [Event("jit_add_frames(9)", 2050 * us, 2130 * us)])
    spans = [Event("bench:force", 0.0, 10 * us), Event("bench:force", 3000 * us, 3300 * us)]
    return Trace({f"/device:TPU:{i}": dev for i in range(chips)}, spans)


@pytest.mark.parametrize("text", [HLO, None], ids=["hand-made", "recorded"])
def test_the_programs_own_summary_reads_the_same_scopes(text):
    """`/varz?trace=1` (``profiling.hlo_stages``) stops at an instruction's own
    scope; this reader goes on to hand unscoped ones to their neighbours' stage.  On
    every instruction with a scope the two agree, and they differ nowhere else than
    on what the program calls ``other``."""
    from ape_x_dqn_tpu.utils import profiling

    if text is None:
        text = open(os.path.join(DATA, "small_stage.hlo.txt")).read()
    mine, _mixed = st.instruction_stages(text)
    theirs = profiling.hlo_stages(text)
    assert set(mine) == set(theirs)
    scoped = {n for n, stage in theirs.items() if stage != profiling.OTHER}
    assert len(scoped) > 10 and all(mine[n] == theirs[n] for n in scoped)
    handed_on = {n for n in mine if mine[n] != theirs[n]}
    assert handed_on and handed_on.isdisjoint(scoped)


@pytest.mark.parametrize("chips", [1, 4])
def test_the_eight_sums_add_up_to_the_programs_time(chips):
    r = types.SimpleNamespace(trace=_trace(chips), fused_program="jit_fused", trace_reduce=tr,
                              config={"steps_per_call": 2})
    ops, fused_s, other_s, runs = st.op_seconds(r.trace, "jit_fused", tr)
    # the ingest program's 80 us lie in the one period between the two whole runs: 80 us
    # a call, times the two runs
    assert runs == 2 and fused_s == pytest.approx(2000e-6) and other_s == pytest.approx(160e-6)
    assert ops["while.13"] == pytest.approx(2 * 20e-6)  # its own time, not its body's
    never = iter(["HloModule unrelated\n", HLO, None])  # the third is not reached
    secs, named, mixed = st.stage_seconds(ops, fused_s, never)
    assert next(never) is None
    assert named == pytest.approx(1 - 40 / 1940)  # fusion.777 is not in the text
    assert mixed == pytest.approx(400 / 1940)     # fusion.22 holds backward and optimizer
    per_step = {k: v / 4 * 1e6 for k, v in secs.items()}
    assert per_step == pytest.approx({
        "ingest": 50, "gather": 175, "forward": 65, "backward": 120, "optimizer": 40,
        "restamp": 5, "target_sync": 10,
        "other": 10 + 10 + 15})  # the while's own, the unnamed op, time with no op
    assert sum(secs.values()) == pytest.approx(fused_s)

    # the readers: the program's texts come from the program, here handed in
    r._stage_table = None
    del r._stage_table
    st_texts = st.program_texts
    try:
        st.program_texts = lambda name: [HLO]
        assert st.read(r, "ingest") == pytest.approx(50 + 80 / 2)  # + the ingest program
        assert st.read(r, "optimizer", "target_sync") == pytest.approx(50)
        assert st.read_rest(r) == pytest.approx(35)
        total = sum(st.read(r, s) for s in st.READ_BY_NAME) + st.read_rest(r)
        fused_us, _ = tr.module_seconds(r.trace, "jit_fused", *tr.span_window(r.trace))
        assert total == pytest.approx(fused_us / 4 * 1e6 + 40)
    finally:
        st.program_texts = st_texts


def test_a_program_that_keeps_no_text_gives_no_metric(monkeypatch):
    r = types.SimpleNamespace(trace=_trace(), fused_program="jit_fused", trace_reduce=tr,
                              config={"steps_per_call": 2})
    monkeypatch.setattr(st, "program_texts", lambda name: [])
    assert st.table(r) is None and st.read(r, "gather") is None and st.read_rest(r) is None
    # as the parent of PR 26 does: the module is there, the function is not
    import ape_x_dqn_tpu.utils.profiling as profiling
    monkeypatch.undo()
    monkeypatch.delattr(profiling, "fused_hlo_texts")
    assert list(st.program_texts("jit_fused")) == []


def test_every_stage_metric_has_a_reader_file_and_an_entry():
    import manifest as mf

    m = mf.load_manifest()
    names = [e["name"] for e in m["per_layer"] if e["name"].endswith("_us_per_step")]
    assert len(names) == 8
    cells = [w["name"] for w in m["workloads"] if w["name"].endswith(".learner")]
    for e in m["per_layer"]:
        if e["name"] in names:
            assert (e["unit"], e["better"], e["source"], e["moves"]) == (
                "us", "lower", "device_trace", "learn_samples_per_s")
            assert e["workloads"] == cells
            assert os.path.isfile(os.path.join(mf.HERE, "layer_metrics", e["name"] + ".py"))


def test_recorded_tpu_pair_reduces_to_the_pinned_table(monkeypatch):
    """chiprun_out of PR 26's first chip call: the toy dedup cell, two whole runs of
    jit_fused (K=4) in the window, beside the dedup layout's two ingest programs, the
    key split and an unstack."""
    trace = tr.load(os.path.join(DATA, "small_stage.xplane.pb"))
    text = open(os.path.join(DATA, "small_stage.hlo.txt")).read()
    assert os.path.getsize(os.path.join(DATA, "small_stage.xplane.pb")) + len(text) < 2_000_000
    monkeypatch.setattr(st, "program_texts", lambda name: [text] if name == "jit_fused" else [])
    r = types.SimpleNamespace(trace=trace, fused_program="jit_fused", trace_reduce=tr,
                              config={"steps_per_call": 4})
    table = st.table(r)
    assert table == pytest.approx({
        "ingest": 52.9875, "sample": 4.62725, "gather": 47.88275, "restamp": 0.838375,
        "forward": 24.03125, "backward": 12.634, "optimizer": 3.215875,
        "target_sync": 0.116625, "other": 11.19725}, rel=1e-4)
    # every traced op of the fused program is an instruction of the text
    ops, fused_s, other_s, runs = st.op_seconds(trace, "jit_fused", tr)
    stages, _mixed = st.instruction_stages(text)
    assert runs == 2 and all(name in stages for name in ops)
    # the eight add up to fused.us_per_step + the other programs' time per step
    fused_us = fused_s / (runs * 4) * 1e6
    assert fused_us == pytest.approx(104.543375, rel=1e-6)
    total = sum(st.read(r, s) for s in st.READ_BY_NAME) + st.read_rest(r)
    assert total == pytest.approx(fused_us + other_s / (runs * 4) * 1e6, rel=1e-9)
    # the frame ring's re-layout copy carries no scope; only the gather consumes it
    ring_copies = [n for n, s in ops.items() if n.startswith("copy") and stages[n] == "gather"]
    assert ring_copies and max(ops[n] for n in ring_copies) > 0.2 * sum(
        s for n, s in ops.items() if stages[n] == "gather")
    # on a program that keeps no text (the parent of PR 26) the readers give nothing
    monkeypatch.setattr(st, "program_texts", lambda name: [])
    del r._stage_table
    assert st.read(r, "gather") is None
