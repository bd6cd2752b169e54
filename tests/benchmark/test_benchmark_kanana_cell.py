"""The cell ``kanana2_q_ep8.learner``: the accepted ``latent.*`` readers on a
hand-made program text and trace of this family (six parts and ``rest`` that
add up to the program's time), the operation count against a count by hand,
the manifest's appended entries with the order held relative, the limits file
against the readings it states, and a copy of the configuration at small
widths for the reference's tests (``test_benchmark_kanana_reference.py``)."""
import json
import os
import types

import pytest

import manifest as mf
import parts_times as pt
import stage_times as st
import trace_reduce as tr
from trace_reduce import DeviceTrace, Event, Trace

CELL = "kanana2_q_ep8.learner"
CONFIG = "kanana2_q_ep8"
PARTS = ["mixer", "attn_latent", "shared_expert", "router", "experts", "dense_ffn"]
STAGE_LISTS = {
    "replay.ingest_us_per_step", "replay.sample_us_per_step", "replay.gather_us_per_step",
    "replay.restamp_us_per_step", "learner.forward_us_per_step", "learner.backward_us_per_step",
    "learner.optimizer_unfused_us_per_step", "fused.other_us_per_step"}
SHARED_LISTS = STAGE_LISTS | {
    "torso.mfu_pct", "torso.experts_roofline", "moe.held_pairs_per_step", "moe.load_max_over_mean",
    "blocks.attn_blocks_visited_pct"}
LATENT_LISTS = {"latent." + n for n in (
    "mixer_step_us", "attn_latent_step_us", "shared_expert_step_us", "router_step_us",
    "experts_step_us", "dense_ffn_step_us", "rest_step_us", "attn_latent_roofline")}

SMALL = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=4, head_dim=8, kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, qk_head_dim=24, num_hidden_layers=3, layers_held=[0, 1, 2],
    published=dict(num_hidden_layers=48, n_routed_experts=16),
    n_routed_experts=4, router_outputs=16, experts_held=[4, 8], num_experts_per_tok=3,
    obs_shape=[44, 44, 10], hidden=32, channels=[8, 8, 8], batch_size=8, replay_capacity=512,
    steps_per_call=1, ingest_block=16, target_sync_freq=8, num_actions=6,
)

# At hidden 64, 40 tokens and batch 8 on the CPU, as the Ling cell's toy: the
# limits are this test's alone and its seed is fixed.  Read while writing this,
# seeds 2**31 + 9 and 2**31 + 77 (priority / median / update): the program
# 0.022-0.028 / 0.014-0.018 / 0.026-0.045; gather_one_row_on 0.877-0.887 /
# 0.662-0.680 / 0.275-0.338, fp8_activations' median 0.351-0.356, bf16_held's
# update 0.360-0.399.
TOY_LIMITS = {"fused_priority_rel": 0.2, "fused_priority_median_rel": 0.1,
              "fused_update_rel": 0.15}

_OP = "jit(fused)/while/body/{}(stage:forward){}/KananaMoeQ/"
_F, _B = _OP.format("jvp", ""), _OP.format("transpose(jvp", ")")
HLO = f"""HloModule jit_fused, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  ROOT %neg.1 = f32[4]{{0}} negate(%p), metadata={{op_name="jit(fused)/stage:sample/neg"}}
}}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {{
  %t = (s32[], f32[4]{{0}}) parameter(0)
  %x = f32[4]{{0}} get-tuple-element(%t), index=1
  %fusion.17 = f32[4]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layer_0/torso:mixer/latent_attention/dot_general"}}
  %constant.19 = s32[4]{{0}} constant({{0, 1, 2, 3}}), metadata={{op_name="{_F}layer_0/torso:mixer/latent_attention/torso:attn_latent/pallas_call"}}
  %attn_fwd.20 = f32[4]{{0}} custom-call(%constant.19, %fusion.17), custom_call_target="tpu_custom_call", operand_layout_constraints={{f32[4]{{0}}}}
  %fusion.18 = f32[4]{{0}} fusion(%attn_fwd.20), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layer_0/torso:dense_ffn/dense/dot_general"}}
  %fusion.21 = f32[4]{{0}} fusion(%fusion.18), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_1_5/while/body/torso:mixer/latent_attention/mul"}}
  %constant.22 = s32[4]{{0}} constant({{0, 1, 2, 3}}), metadata={{op_name="{_F}layers_1_5/while/body/torso:mixer/latent_attention/torso:attn_latent/pallas_call"}}
  %attn_fwd.23 = f32[4]{{0}} custom-call(%constant.22, %fusion.21), custom_call_target="tpu_custom_call", operand_layout_constraints={{f32[4]{{0}}}}
  %fusion.24 = f32[4]{{0}} fusion(%attn_fwd.23), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{_B}layers_1_5/while/body/torso:mixer/latent_attention/torso:attn_latent/reduce_sum"}}
  %fusion.25 = f32[4]{{0}} fusion(%fusion.24), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_1_5/while/body/torso:router/moe/reduce_max"}}
  %fusion.26 = f32[4]{{0}} fusion(%fusion.25), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_1_5/while/body/moe/torso:experts/ragged_dot"}}
  %fusion.27 = f32[4]{{0}} fusion(%fusion.26), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_1_5/while/body/torso:shared_expert/shared_expert/dot_general"}}
  %fusion.28 = f32[4]{{0}} fusion(%fusion.27), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_B}layers_1_5/while/body/torso:mixer/latent_attention/transpose(jvp(dot_general))"}}
  %fusion.31 = f32[4]{{0}} fusion(%fusion.28), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(fused)/while/body/stage:optimizer/sub"}}
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
OPS = [("fusion.9", 0, 50), ("while.13", 50, 900), ("fusion.17", 55, 45), ("attn_fwd.20", 100, 50),
       ("fusion.18", 150, 20), ("fusion.21", 170, 40), ("attn_fwd.23", 210, 80),
       ("fusion.24", 290, 30), ("fusion.25", 330, 170), ("fusion.26", 500, 100),
       ("fusion.27", 600, 40), ("fusion.28", 640, 160), ("fusion.31", 820, 100)]
WANT = {"mixer": 45 + 40 + 160, "attn_latent": 50 + 80 + 30, "shared_expert": 40, "router": 170,
        "experts": 100, "dense_ffn": 20}


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


def _config():
    return mf.load_json(os.path.join(mf.HERE, "configs", CONFIG + ".json"))


def _readings(**over):
    base = dict(trace=_trace(), fused_program="jit_fused", trace_reduce=tr, config=_config(),
                counters={"held_pairs_per_step": 141120.0, "load_max_per_step": 30.0,
                          "load_mean_per_step": 20.0,
                          "attention_blocks_visited_latent_per_step": 3 * 8 * 6 * 32 * 28.0,
                          "attention_blocks_total_latent_per_step": 3 * 8 * 6 * 32 * 52.0},
                end_to_end={"learn_samples_per_s": 12.0},
                peaks=json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"])
    return types.SimpleNamespace(**dict(base, **over))


def test_the_six_parts_and_the_rest_add_up_to_the_programs_time(monkeypatch):
    import ops_count_kanana2_q as ops

    monkeypatch.setattr(st, "program_texts", lambda name: ["HloModule unrelated\n", HLO])
    r = _readings()
    assert r.config["parts"] == PARTS and r.config["parts_scope"] == "torso:attn_latent"
    assert r.config["parts_prefix"] == "latent"
    table = pt.table(r)
    assert {k: v for k, v in table.items() if k != "rest"} == pytest.approx(WANT)
    # the gather, the optimizer, the while's own time and the time with no op, the ingest's 80 us
    assert table["rest"] == pytest.approx(50 + 100 + 65 + 50 + 80)
    fused_us, runs = tr.module_seconds(r.trace, "jit_fused", *tr.span_window(r.trace))
    assert runs == 2 and sum(table.values()) == pytest.approx(fused_us / 2 * 1e6 + 80)
    cell = mf.Cell(mf.load_manifest(), CELL)
    mine = [m["name"] for m in cell.per_layer() if m["name"].startswith("latent.")]
    assert set(mine) == LATENT_LISTS               # not the two latent.delta_scan_*: no such part
    got = {n: cell.reader(n)(r) for n in mine}
    steps = [n for n in got if n.endswith("_step_us")]
    assert len(steps) == 7 and sum(got[n] for n in steps) == pytest.approx(sum(table.values()))
    assert {n[len("latent."):-len("_step_us")] for n in steps} == set(PARTS) | {"rest"}
    assert got["latent.attn_latent_roofline"] == pytest.approx(
        ops.attention_floor_s(r.config, r.peaks, "latent")[0] / (WANT["attn_latent"] * 1e-6) * 100)
    # the accepted readers this cell is appended to read it by the configuration's names
    assert cell.reader("torso.mfu_pct")(r) == pytest.approx(
        ops.flops_per_sample(r.config, 141120.0) * 12.0 / 197e12 * 100)
    assert 20 < cell.reader("torso.mfu_pct")(r) < 40
    assert cell.reader("moe.held_pairs_per_step")(r) == 141120.0
    assert cell.reader("moe.load_max_over_mean")(r) == pytest.approx(1.5)
    assert cell.reader("blocks.attn_blocks_visited_pct")(r) == pytest.approx(28 / 52 * 100)
    assert cell.reader("latent.delta_scan_step_us")(r) is None  # no such part here: not on that list


@pytest.mark.parametrize("name", sorted(LATENT_LISTS))
def test_a_program_without_the_scope_gives_no_metric(monkeypatch, name):
    """The parent's program of this cell does not exist, and a program with no
    ``torso:attn_latent`` gives no table: every reader returns nothing and
    raises nothing."""
    monkeypatch.setattr(st, "program_texts", lambda name: [HLO.replace("torso:attn_latent", "torso:x")])
    assert mf.Cell(mf.load_manifest(), CELL).reader(name)(_readings()) is None


def test_the_count_is_the_hand_count():
    """ISSUE 56's table of parameters and floors, and the latent layers' floor
    against a count by hand at a small shape."""
    import ops_count_kanana2_q as ops
    import reference.kanana2_q as ref

    cfg = _config()
    assert ops.mixer_param_count(cfg) == (
        12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608) == 26_345_984
    assert ops.expert_layer_param_count(cfg) == 262_144 + 128 + 9_437_184 + 16 * 4_718_592
    dense_layer = ops.mixer_param_count(cfg) + 3 * 2048 * 6144 + 2 * 2048
    expert_layer = ops.mixer_param_count(cfg) + ops.expert_layer_param_count(cfg) + 2 * 2048
    assert (dense_layer, expert_layer) == (64_098_816, 111_547_008)
    assert ops.layers_param_count(cfg) == dense_layer + 5 * expert_layer == 621_833_856
    assert ops.param_count(cfg) == ref.param_count(cfg) == 624_146_739
    assert ops.tokens_per_sample(cfg) == 1568 and ops.pairs_in_mask(cfg) == 1_230_096
    assert (ops.layers_of(cfg, "latent_attention"), ops.layers_of(cfg, "moe"),
            ops.layers_of(cfg, "dense")) == (6, 5, 1)
    assert ops.expected_pairs_per_step(cfg) == pytest.approx(3 * 8 * 1568 * 6 * 16 / 128 * 5) == 141_120
    peaks = json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"]
    # six layers of 32 heads are 24 times Ling's one layer of 8: 24 x 1.28 ms, compute-bound
    import ops_count_ling3_q as ling_ops

    ling = mf.load_json(os.path.join(mf.HERE, "configs", "ling3_q_l7.json"))
    floor, bound = ops.attention_floor_s(cfg, peaks, "latent")
    assert bound == "compute" and floor == pytest.approx(24 * ling_ops.attention_floor_s(ling, peaks)[0])
    assert floor == pytest.approx(0.0307, rel=2e-3)
    assert ops.step_flops(cfg, 141_120.0) == pytest.approx(38.94e12, rel=1e-3)
    assert ops.step_floor_s(cfg, peaks, 141_120.0)[1] == "compute"
    # a forward's matrix products a token, by hand: W_q, W_dkv, W_ukv, W_o
    assert ops.mixer_macs_per_token(cfg) == 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048
    assert ops.macs_per_token(cfg) == dict(
        tokens=64 * 2048, mixer=6 * 26_345_472, router=5 * 2048 * 128,
        shared_expert=5 * 3 * 2048 * 1536, dense_ffn=3 * 2048 * 6144)
    # by hand: one latent layer, 2 heads of 16 + 8 against values of 12, 20 tokens, batch 2
    small = dict(cfg, layers_held=[5], batch_size=2, obs_shape=[44, 44, 5], num_attention_heads=2,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12)
    assert ops.tokens_per_sample(small) == 20 and ops.layers_of(small, "latent_attention") == 1
    macs = 2 * (16 + 8 + 12) * (20 * 21 // 2)
    assert ops.attention_macs_per_sample(small) == macs
    fast = {"flops_per_s_bf16": 1e9, "hbm_bytes_per_s": 1e15}
    assert ops.attention_floor_s(small, fast) == (pytest.approx(5 * 2 * macs * 2 / 1e9), "compute")
    slow = {"flops_per_s_bf16": 1e18, "hbm_bytes_per_s": 1e6}
    operands = 2 * 24 + 2 * 16 + 8 + 2 * 12                     # q, k, the one shared key, v
    forward, backward = 20 * (operands + 24) * 2, 20 * (2 * operands + 2 * 24) * 2
    assert ops.attention_floor_s(small, slow) == (
        pytest.approx(2 * (3 * forward + backward) / 1e6), "bandwidth")
    # the experts' floor from the pairs really routed: FLOPs at even loads, the weights' reads at few
    assert ops.expert_floor_s(cfg, peaks, 141_120.0)[1] == "compute"
    assert ops.expert_floor_s(cfg, peaks, 1000.0)[1] == "bandwidth"


def test_the_manifests_appended_entries():
    m = mf.load_manifest()
    cell = mf.Cell(m, CELL)
    assert cell.chips == 1 and cell.traffic_name == "learner_feed_collected"
    assert cell.config["network"] == "kanana_moe" and cell.config["reference"] == "kanana2_q"
    assert cell.config["ops_count"] == "ops_count_kanana2_q"
    entry = [c for c in m["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "replay_capacity"]
    assert entry["source"] == cell.config["source"] == (
        "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/kanana2_q_ep8.json"
    listed = {x["name"] for x in m["per_layer"] if CELL in x.get("workloads", ())}
    assert listed == SHARED_LISTS | LATENT_LISTS
    assert not any(CELL in x["workloads"] for x in m["per_layer"]
                   if x["name"] in ("latent.delta_scan_step_us", "latent.delta_scan_roofline"))
    reported = {x["name"] for x in cell.per_layer()}
    assert {"ingest.ms_per_call", "fused.us_per_step", "device.idle_pct", "device.peak_hbm_gb",
            "pass.bootstrap_step_us", "pass.forward_step_us", "pass.recompute_step_us",
            "pass.backward_step_us", "pass.walk_recompute_step_us",
            "pass.walk_backward_step_us"} <= reported
    assert not any(n.startswith(("linear.", "blocks.attn_full", "hybrid.", "gdn.")) for n in reported)
    assert [x["name"] for x in cell.end_to_end()] == ["learn_samples_per_s", "setup_s"]
    # ten cells, one of them on four chips; nothing but this cell is new
    assert len(m["workloads"]) == 10 and [w["chips"] for w in m["workloads"]].count(4) == 1
    assert len(m["configs"]) == 10 and len(m["per_layer"]) == 69
    # the order the contract asks for, held relative so that a later cell appended after this
    # one breaks nothing: this PR's entries follow the Olmo cell's and, on a list, the Ling cell's
    configs, cells = [x["name"] for x in m["configs"]], [x["name"] for x in m["workloads"]]
    assert configs.index("olmoh_q_l4") < configs.index(CONFIG)
    assert cells.index("olmoh_q_l4.learner") < cells.index(CELL)
    for x in m["per_layer"]:
        if CELL in x.get("workloads", ()):
            others = [w for w in x["workloads"] if w != CELL]
            assert x["workloads"].index(CELL) > max(x["workloads"].index(w) for w in others), x["name"]
            assert "ling3_q_l7.learner" in others, x["name"]     # the other latent cell is on each
            assert x["moves"] == "learn_samples_per_s", x["name"]


def test_the_configuration_is_the_catalogs_row_but_for_the_cut():
    """Every number of the published ``config.json`` under its key; the two
    keys the cut changes are in ``reduced`` and under ``published``; no width
    differs."""
    c = _config()
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    cut = {"num_hidden_layers": 6, "n_routed_experts": 16}
    for key, value in published.items():
        assert c[key] == cut.get(key, value), key
        assert (key in c["reduced"]) == (key in cut), key
    assert c["published"] == {k: published[k] for k in cut}
    assert (c["layers_held"], c["experts_held"], c["router_outputs"]) == ([0, 1, 2, 3, 4, 5], [0, 16], 128)
    assert "heads_held" not in c                    # no head is divided
    assert c["layer_types"] == ["latent_attention"] * 48
    assert set(c["reduced_why"]) == set(c["reduced"]) == set(cut) | {"replay_capacity"}
    assert {"shared_experts", "expert_bias", "router", "latent_layer", "initialisation",
            "tokenisation", "timed_state"} <= set(c["assumed"])
    assert "8 chips share every layer" in c["deployment"] and "no head is divided" in c["deployment"]
    assert (c["batch_size"], c["steps_per_call"], c["ingest_block"], c["chips"],
            c["replay_capacity"]) == (8, 1, 16, 1, 4096)
    laguna = mf.load_json(os.path.join(mf.HERE, "configs", "laguna_q_ep32.json"))
    for key in ("channels", "hidden", "obs_shape", "num_actions", "n_step", "gamma", "replay_layout",
                "frame_ratio", "target_sync_freq", "optimizer", "learning_rate", "rmsprop_decay",
                "rmsprop_eps", "max_grad_norm", "loss", "priority_exponent", "is_exponent",
                "precision", "sample_ahead"):
        assert c[key] == laguna[key], key           # around the block everything is Laguna's


def test_the_limits_lie_between_their_readings():
    """``limits/kanana2_q_ep8.json`` under the accepted manifest test's rule,
    and what it says of each control."""
    import reference.kanana2_q as ref

    limits = mf.load_json(os.path.join(mf.HERE, "limits", CONFIG + ".json"))
    assert set(limits) == set(TOY_LIMITS)
    for name, v in limits.items():
        assert 0 < v["sound_max"] < v["limit"] < v["control_min"], name
        assert 3 * v["sound_max"] <= v["control_min"], name
        assert v["control"] in ("bf16_held", "fp8_activations", "gather_one_row_on") + ref.FLAGS, name
        # the file says of every control, this reference's three flags among them, which number sees it
        assert set(v["seen_by"]) == set(ref.FLAGS) | {"bf16_held", "fp8_activations", "gather_one_row_on"}
        assert "TPU v5 lite" in v["readings"] and "PR 56" in v["readings"], name
    assert limits["fused_update_rel"]["control"] == "bf16_held"
    # the nearest precision below the stated one and the lost shared key are kept out on every seed read
    for control in ("bf16_held", "reference_drops_shared_key"):
        assert limits["fused_update_rel"]["seen_by"][control].startswith("3 of 3"), control
    # the number that admits a flipped argmax says so: its sound_max leaves the flip out
    flipped = limits["fused_priority_rel"]
    assert flipped["sound_max"] < flipped["reading_max"] < flipped["limit"] and "NOT the largest" in flipped["sound_max_is"]
    assert not any("reading_max" in v for k, v in limits.items() if k != "fused_priority_rel")


def test_what_the_ling_cells_pinned_test_holds_beside_its_pin(monkeypatch):
    """``test_benchmark_ling_cell.test_the_manifests_new_entries`` pins every
    ``latent.*`` list to the Ling cell alone; PR 51's test ran it with the
    seven lists its own cell stands on cut to their first cell, and this cell
    stands on seven more (``tests/conftest.py`` marks both expected to fail,
    with the reason).  On the manifest with every ``latent.*`` and
    ``linear.*`` list cut to the cell it was written for, whoever was appended
    since, the test runs as it stands: what it holds beside that pin is held
    here, and a cell appended later breaks nothing."""
    import test_benchmark_ling_cell as ling

    def cut():
        m = _load_manifest()
        for x in m["per_layer"]:
            if x["name"].startswith(("latent.", "linear.")):
                x["workloads"] = x["workloads"][:1]
        return m

    _load_manifest = mf.load_manifest
    assert any(CELL in x["workloads"][1:] for x in _load_manifest()["per_layer"]
               if x["name"].startswith("latent."))
    monkeypatch.setattr(mf, "load_manifest", cut)
    ling.test_the_manifests_new_entries()


def _toy_config(**over):
    return dict(_config(), **SMALL, **over)


def _toy_traffic():
    traffic = mf.load_json(os.path.join(mf.HERE, "traffic", "learner_feed_collected.json"))
    traffic["check"] = dict(traffic["check"], ring_rows_per_chip=256, ingest_rows_per_chip=32)
    return traffic
