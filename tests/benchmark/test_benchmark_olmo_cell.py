"""The cell ``olmoh_q_l4.learner``: the accepted ``linear.*`` readers and
``latent.dense_ffn_step_us`` (which read the configuration's own ``parts``)
and the one new reader, ``gdn.mfu_pct``, on a hand-made program text and
trace (the five times add up, the two roofline shares and the whole step's
share are the count's), a program without the cell's torso gives none of
the parts, the operation count against a count by hand, and the manifest's
new entries, their order held relative (``names.index``)."""
import json
import os
import types

import pytest

import manifest as mf
import parts_times as pt
import stage_times as st
import trace_reduce as tr
from trace_reduce import DeviceTrace, Event, Trace

CELL = "olmoh_q_l4.learner"
PARTS = ["delta_scan", "mixer", "attn_full", "dense_ffn"]
SHARED_LISTS = {
    "replay.ingest_us_per_step", "replay.sample_us_per_step", "replay.gather_us_per_step",
    "replay.restamp_us_per_step", "learner.forward_us_per_step", "learner.backward_us_per_step",
    "learner.optimizer_unfused_us_per_step", "fused.other_us_per_step",
    "blocks.attn_blocks_visited_pct"}
# the accepted readers this cell is appended to: they read the configuration's ``parts`` by name
STEPS = {"delta_scan": "linear.delta_scan_step_us", "mixer": "linear.mixer_step_us",
         "attn_full": "linear.attn_full_step_us", "dense_ffn": "latent.dense_ffn_step_us",
         "rest": "linear.rest_step_us"}
ROOFLINES = ["linear.delta_scan_roofline", "linear.attn_full_roofline"]
PARTS_LISTS = set(STEPS.values()) | set(ROOFLINES)
MFU = "gdn.mfu_pct"

_OP = "jit(fused)/while/body/{}(stage:forward){}/OlmoHybridQ/"
_F, _B = _OP.format("jvp", ""), _OP.format("transpose(jvp", ")")
_SCAN = "layers_0_2/torso:mixer/linear_attention/torso:delta_scan/while/body/scalar_gate/"
HLO = f"""HloModule jit_fused, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  ROOT %neg.1 = f32[4]{{0}} negate(%p), metadata={{op_name="jit(fused)/stage:sample/neg"}}
}}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {{
  %t = (s32[], f32[4]{{0}}) parameter(0)
  %x = f32[4]{{0}} get-tuple-element(%t), index=1
  %fusion.17 = f32[4]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_0_2/torso:mixer/linear_attention/dot_general"}}
  %fusion.18 = f32[4]{{0}} fusion(%fusion.17), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}{_SCAN}dot_general"}}
  %fusion.19 = f32[4]{{0}} fusion(%fusion.18), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_0_2/torso:mixer/operator_norm/rsqrt"}}
  %fusion.20 = f32[4]{{0}} fusion(%fusion.19), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_0_2/torso:dense_ffn/dense/dot_general"}}
  %constant.21 = s32[4]{{0}} constant({{0, 1, 2, 3}}), metadata={{op_name="{_F}layer_3/torso:mixer/full_attention/torso:attn_full/pallas_call"}}
  %attn_fwd.22 = f32[4]{{0}} custom-call(%constant.21, %fusion.20), custom_call_target="tpu_custom_call", operand_layout_constraints={{f32[4]{{0}}}}
  %fusion.23 = f32[4]{{0}} fusion(%attn_fwd.22), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layer_3/torso:mixer/full_attention/dot_general"}}
  %fusion.24 = f32[4]{{0}} fusion(%fusion.23), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layer_3/torso:dense_ffn/ffn_norm/rsqrt"}}
  %fusion.25 = f32[4]{{0}} fusion(%fusion.24), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_B}{_SCAN}transpose(jvp(dot_general))"}}
  %fusion.31 = f32[4]{{0}} fusion(%fusion.25), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(fused)/while/body/stage:optimizer/sub"}}
  %i = s32[] get-tuple-element(%t), index=0
  ROOT %out = (s32[], f32[4]{{0}}) tuple(%i, %fusion.31)
}}

ENTRY %main.3 (ring: f32[4]) -> f32[4] {{
  %ring = f32[4]{{0}} parameter(0), metadata={{op_name="replay_state.rows"}}
  %fusion.9 = f32[4]{{0}} fusion(%ring), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(fused)/stage:gather/gather"}}
  %zero = s32[] constant(0)
  %init = (s32[], f32[4]{{0}}) tuple(%zero, %fusion.9)
  %while.13 = (s32[], f32[4]{{0}}) while(%init), condition=%cond.4, body=%body.2, metadata={{op_name="jit(fused)/while"}}
  ROOT %res = f32[4]{{0}} get-tuple-element(%while.13), index=1
}}
"""
# microseconds of each instruction in one run of the program (K = 1)
OPS = [("fusion.9", 0, 50), ("while.13", 50, 900), ("fusion.17", 55, 95), ("fusion.18", 150, 60),
       ("fusion.19", 210, 15), ("fusion.20", 225, 200), ("attn_fwd.22", 425, 25),
       ("fusion.23", 450, 70), ("fusion.24", 520, 20), ("fusion.25", 540, 140),
       ("fusion.31", 800, 100)]
WANT = {"delta_scan": 60 + 140, "mixer": 95 + 15 + 70, "attn_full": 25, "dense_ffn": 200 + 20}


def _trace(ops=OPS):
    """Two whole runs of 1,000 us, one cut by the window's start, and an
    ingest program of 80 us between them."""
    us = 1e-6
    starts = (-500, 1000, 2200)
    dev = DeviceTrace(
        ops=[Event(f"%{n} = f32[4]{{0}} fusion(%x)", (t0 + s) * us, (t0 + s + d) * us)
             for t0 in starts for n, s, d in ops]
        + [Event("%add.1 = s32[] add(%a, %b)", 2050 * us, 2130 * us)],
        async_ops=[],
        modules=[Event("jit_fused(123)", t0 * us, (t0 + 1000) * us) for t0 in starts]
        + [Event("jit_add_frames(9)", 2050 * us, 2130 * us)])
    spans = [Event("bench:force", 0.0, 10 * us), Event("bench:force", 3000 * us, 3300 * us)]
    return Trace({"/device:TPU:0": dev}, spans)


def _readings(**over):
    cfg = mf.load_json(os.path.join(mf.HERE, "configs", "olmoh_q_l4.json"))
    base = dict(trace=_trace(), fused_program="jit_fused", trace_reduce=tr, config=cfg,
                counters={"attention_blocks_visited_full_per_step": 3 * 4 * 30 * 28.0,
                          "attention_blocks_total_full_per_step": 3 * 4 * 30 * 52.0},
                end_to_end={"learn_samples_per_s": 5.0},
                peaks=json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"])
    return types.SimpleNamespace(**dict(base, **over))


def test_the_five_times_add_up_to_the_programs_time(monkeypatch):
    import ops_count_olmoh_q as ops

    monkeypatch.setattr(st, "program_texts", lambda name: ["HloModule unrelated\n", HLO])
    r = _readings()
    assert r.config["parts"] == PARTS and r.config["parts_scope"] == "torso:delta_scan"
    assert "parts_prefix" not in r.config     # no reader file of this cell's own names a prefix
    table = pt.table(r)
    # the post-norms are read where they are scoped: the mixer's output's under ``mixer``, the
    # FFN's under ``dense_ffn``; the scalar-gate walk, forward and pulled back, under ``delta_scan``
    assert {k: v for k, v in table.items() if k != "rest"} == pytest.approx(WANT)
    # the gather, the optimizer, the while's own time, the time with no op,
    # and the ingest program's 80 us a call
    assert table["rest"] == pytest.approx(50 + 100 + 175 + 50 + 80)
    fused_us, runs = tr.module_seconds(r.trace, "jit_fused", *tr.span_window(r.trace))
    assert runs == 2 and sum(table.values()) == pytest.approx(fused_us / 2 * 1e6 + 80)
    cell = mf.Cell(mf.load_manifest(), CELL)
    reported = {m["name"] for m in cell.per_layer()}
    assert PARTS_LISTS | {MFU} <= reported
    got = {n: cell.reader(n)(r) for n in PARTS_LISTS | {MFU}}
    assert set(STEPS) == set(PARTS) | {"rest"}
    assert {part: got[name] for part, name in STEPS.items()} == pytest.approx(table)
    assert sum(got[n] for n in STEPS.values()) == pytest.approx(sum(table.values()))
    assert got["linear.delta_scan_roofline"] == pytest.approx(
        ops.delta_floor_s(r.config, r.peaks)[0] / (WANT["delta_scan"] * 1e-6) * 100)
    assert got["linear.attn_full_roofline"] == pytest.approx(
        ops.attention_floor_s(r.config, r.peaks)[0] / (WANT["attn_full"] * 1e-6) * 100)
    assert got[MFU] == pytest.approx(ops.flops_per_sample(r.config) * 5.0 / 197e12 * 100)
    assert 25 < got[MFU] < 45          # 13.3 TFLOP a sample: 5 samples/s are a third of the peak
    assert cell.reader("blocks.attn_blocks_visited_pct")(r) == pytest.approx(28 / 52 * 100)


def test_a_program_without_the_cells_torso_gives_none_of_the_parts(monkeypatch):
    """The parent has no ``olmo_hybrid``; a program whose text lacks the
    marking scope gives none of the parts, a run that read no rate no share
    of the peak, and nothing raises."""
    monkeypatch.setattr(st, "program_texts", lambda name: [HLO.replace("torso:delta_scan", "torso:x")])
    cell = mf.Cell(mf.load_manifest(), CELL)
    for name in sorted(PARTS_LISTS):
        assert cell.reader(name)(_readings()) is None, name
    assert cell.reader(MFU)(_readings(end_to_end={})) is None


def test_the_count_is_the_hand_count():
    """ISSUE 51's arithmetic: the parameters to the unit, the pairs, the
    scalar form's products and bytes at the published head sizes."""
    import ops_count_olmoh_q as ops
    import reference.olmoh_q as ref

    cfg = mf.load_json(os.path.join(mf.HERE, "configs", "olmoh_q_l4.json"))
    assert ops.param_count(cfg) == ref.param_count(cfg) == 836_784_807
    assert ops.mixer_macs_per_token(cfg, "linear_attention") == (
        3840 * (2880 + 2880 + 5760 + 5760 + 5760) + (2880 + 2880 + 5760) * 4 + 2 * 3840 * 30)
    assert ops.mixer_macs_per_token(cfg, "full_attention") == 4 * 3840 * 3840
    assert ops.tokens_per_sample(cfg) == 1568 and ops.pairs_in_mask(cfg) == 1_230_096
    assert ops.pairs_in_chunks(cfg) == 24 * (64 * 65 // 2) + 32 * 33 // 2 == 50_448
    assert (ops.layers_of(cfg, "linear_attention"), ops.layers_of(cfg, "full_attention")) == (3, 1)
    # a pair 96 + 96 + 96 + 192 + 192, a token and head 3 x 96 x 192
    assert ops.delta_macs_per_sample(cfg) == 3 * 30 * (672 * 50_448 + 3 * 96 * 192 * 1568)
    assert ops.attention_macs_per_sample(cfg) == 2 * 128 * 30 * 1_230_096
    peaks = json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"]
    # a pass: q, k (96 each), v, o (192 each) in bfloat16, g and beta 4 B each; five passes, 4 rows
    a_pass = 3 * 1568 * 30 * ((96 + 96 + 192 + 192) * 2 + 8)
    assert ops.delta_floor_s(cfg, peaks) == (pytest.approx(5 * 4 * a_pass / 819e9), "bandwidth")
    assert 3.2e9 < 5 * 4 * a_pass < 3.4e9 and 0.0039 < ops.delta_floor_s(cfg, peaks)[0] < 0.0041
    flops = 5 * 2 * ops.delta_macs_per_sample(cfg) * 4 / 197e12
    assert 0.0020 < flops < 0.0024                                  # the products: under the bytes
    assert ops.attention_floor_s(cfg, peaks) == (
        pytest.approx(5 * 4 * 128 * 30 * 1_230_096 * 4 / 197e12), "compute")
    # the whole step: a forward's products twice their multiply-adds, five forwards' worth less
    # the first convolution's input gradient
    stem, head, first = ops.stem_and_head_flops(cfg)
    forward = (stem + head + 2 * 1568 * sum(ops.macs_per_token(cfg).values())
               + 2 * ops.attention_macs_per_sample(cfg) + 2 * ops.delta_macs_per_sample(cfg))
    assert ops.flops_per_sample(cfg) == 5 * forward - first
    assert 13.0e12 < ops.flops_per_sample(cfg) < 13.5e12
    # by hand at a small shape: one linear layer, 2 heads of keys 3 and values 5, 20 tokens in chunks of 8
    small = dict(cfg, layers_held=[0], batch_size=2, obs_shape=[44, 44, 5], linear_num_key_heads=2,
                 linear_key_head_dim=3, linear_value_head_dim=5, linear_chunk_size=8)
    pairs = 2 * (8 * 9 // 2) + 4 * 5 // 2
    assert ops.tokens_per_sample(small) == 20 and ops.pairs_in_chunks(small) == pairs
    assert ops.delta_macs_per_sample(small) == 2 * ((3 * 3 + 2 * 5) * pairs + 3 * 3 * 5 * 20)
    slow = {"flops_per_s_bf16": 1e18, "hbm_bytes_per_s": 1e6}
    assert ops.delta_floor_s(small, slow) == (
        pytest.approx(5 * 2 * 20 * 2 * ((3 + 3 + 5 + 5) * 2 + 8) / 1e6), "bandwidth")


def test_the_manifests_new_entries():
    m = mf.load_manifest()
    cell = mf.Cell(m, CELL)
    assert cell.chips == 1 and cell.traffic_name == "learner_feed_collected"
    c = cell.config
    assert (c["network"], c["reference"], c["ops_count"]) == (
        "olmo_hybrid", "olmoh_q", "ops_count_olmoh_q")
    entry = [x for x in m["configs"] if x["name"] == "olmoh_q_l4"][0]
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers", "replay_capacity"]
    assert entry["source"].startswith(c["source"]) and c["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert entry["source"].endswith("as the Q-network's torso over a 32-frame history of stem positions")
    assert len(cell.workload["why"]) <= 200 and len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    mine = [x for x in m["per_layer"] if x["name"].startswith("gdn.")]
    assert mine == [dict(name=MFU, unit="%", better="higher", source="host_clock", layer="learner",
                         moves="learn_samples_per_s", workloads=[CELL])]
    listed = {x["name"] for x in m["per_layer"] if CELL in x.get("workloads", ())}
    assert listed == SHARED_LISTS | PARTS_LISTS | {MFU}
    reported = {x["name"] for x in cell.per_layer()}
    assert {"ingest.ms_per_call", "fused.us_per_step", "device.idle_pct",
            "device.peak_hbm_gb"} <= reported
    assert not any(n.startswith(("hybrid.", "torso.", "moe.")) for n in reported)
    assert {n for n in reported if n.startswith(("linear.", "latent."))} == PARTS_LISTS
    assert {x["name"] for x in cell.end_to_end()} == {"learn_samples_per_s", "setup_s"}
    # every published number of the catalog row under its key, but the depth
    assert (c["hidden_size"], c["intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"], c["linear_conv_kernel_dim"],
            c["rms_norm_eps"], c["vocab_size"], c["max_position_embeddings"],
            c["linear_allow_neg_eigval"], c["rope_parameters"], c["model_type"]) == (
                3840, 11008, 30, 30, 30, 30, 96, 192, 4, 1e-6, 100352, 65536, True,
                {"rope_theta": None}, "olmo_hybrid")
    assert c["published"] == {"num_hidden_layers": 32} and c["num_hidden_layers"] == 4
    assert c["layers_held"] == [0, 1, 2, 3] and len(c["layer_types"]) == 32
    assert c["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8
    assert (c["batch_size"], c["steps_per_call"], c["ingest_block"], c["replay_capacity"],
            c["obs_shape"], c["replay_layout"], c["sample_ahead"], c["chips"]) == (
                4, 1, 16, 4096, [84, 84, 32], "dedup", True, 1)
    assert set(c["reduced_why"]) == set(c["reduced"]) and c["departures"].startswith("none")
    assert "836,784,807" in c["reduced_why"]["num_hidden_layers"]
    assert {"block_order", "qk_norm", "positional_rule", "linear_layer", "chunk", "initialisation",
            "tokenisation", "readout", "precision", "optimizer", "recomputation", "timed_state",
            "batch"} <= set(c["assumed"])
    for name in ("block_order", "qk_norm", "positional_rule", "linear_layer"):
        assert "No network here to check" in c["assumed"][name], name
    assert "one stage" not in c["deployment"] and "8 chips as the stages of a pipeline" in c["deployment"]
    # the order the contract asks for, held relative: this PR's entries follow the ling cell's
    # and, on the parts' lists, the cell each was written for; a later cell appended after this
    # one breaks nothing here
    configs, cells = [x["name"] for x in m["configs"]], [x["name"] for x in m["workloads"]]
    assert configs.index("ling3_q_l7") < configs.index("olmoh_q_l4")
    assert cells.index("ling3_q_l7.learner") < cells.index(CELL)
    assert all(x["workloads"].index("ling3_q_l7.learner") < x["workloads"].index(CELL)
               for x in m["per_layer"] if x["name"] in SHARED_LISTS)
    assert all(x["workloads"].index(CELL) > 0 for x in m["per_layer"] if x["name"] in PARTS_LISTS)
    names = [x["name"] for x in m["per_layer"]]
    assert names.index("latent.attn_latent_roofline") < names.index(MFU)
    limits = mf.load_json(os.path.join(mf.HERE, "limits", "olmoh_q_l4.json"))
    assert set(limits) == {"fused_priority_rel", "fused_priority_median_rel", "fused_update_rel"}
    assert all(0 < v["sound_max"] < v["limit"] < v["control_min"] for v in limits.values())
    assert all("TPU v5 lite" in v["readings"] and "PR 51" in v["readings"] for v in limits.values())


@pytest.mark.parametrize("held", ["test_the_manifests_new_entries",
                                  "test_what_the_solar_cells_two_pinned_tests_hold_beside_their_pins"])
def test_what_the_ling_cells_two_pinned_tests_hold_beside_their_pins(held, monkeypatch):
    """Two of ``test_benchmark_ling_cell.py``'s tests pin every ``latent.*``
    and ``linear.*`` list to the one cell each was written for; this cell is
    appended to seven of them and may not edit that file (``tests/conftest.py``
    marks the two expected to fail, with the reason).  On the manifest with
    those seven lists cut to their first cell each runs as it stands, so what
    they hold beside that pin is held here, and a cell appended later breaks
    nothing."""
    import inspect

    import test_benchmark_ling_cell as ling

    def cut():
        m = _load_manifest()
        for x in m["per_layer"]:
            if x["name"] in PARTS_LISTS:
                assert CELL in x["workloads"][1:]
                x["workloads"] = x["workloads"][:1]
        return m

    _load_manifest = mf.load_manifest
    monkeypatch.setattr(mf, "load_manifest", cut)
    test = getattr(ling, held)
    test(monkeypatch) if "monkeypatch" in inspect.signature(test).parameters else test()


def test_the_controls_script_names_the_references_flags():
    import check_gdn_controls
    import reference.olmoh_q as ref

    assert check_gdn_controls.FLAGS == ref.FLAGS
