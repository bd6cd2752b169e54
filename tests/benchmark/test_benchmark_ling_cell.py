"""The cell ``ling3_q_l7.learner``: the ten ``latent.*`` readers on a
hand-made program text and trace, the operation count against a count by
hand, the manifest's new entries (and what the solar cell's two pinned tests
hold beside their pins), and the cell at a toy size on the CPU, where a copy
of its configuration with small widths runs through ``run.measure`` under the
driver ``learner_feed_collected`` and comes out correct, and the reference
with any of its four mechanism flags does not."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import manifest as mf
import parts_times as pt
import stage_times as st
import trace_reduce as tr
from trace_reduce import DeviceTrace, Event, Trace

ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=mf.ROOT,
           XLA_FLAGS="--xla_force_host_platform_device_count=1")
CELL = "ling3_q_l7.learner"
PARTS = ["delta_scan", "mixer", "attn_latent", "shared_expert", "router", "experts", "dense_ffn"]
SHARED_LISTS = {
    "replay.ingest_us_per_step", "replay.sample_us_per_step", "replay.gather_us_per_step",
    "replay.restamp_us_per_step", "learner.forward_us_per_step", "learner.backward_us_per_step",
    "learner.optimizer_unfused_us_per_step", "fused.other_us_per_step", "torso.mfu_pct",
    "torso.experts_roofline", "moe.held_pairs_per_step", "moe.load_max_over_mean",
    "blocks.attn_blocks_visited_pct"}

SMALL = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    qk_head_dim=24, rotary_dim=8,
    published=dict(num_hidden_layers=42, first_k_dense_replace=2, num_experts=32,
                   num_attention_heads=8, num_key_value_heads=8),
    heads_held=[4, 8], num_experts=4, router_outputs=32, experts_held=[4, 8], n_group=4,
    topk_group=2, num_experts_per_tok=4, kda_chunk_size=16,
    obs_shape=[44, 44, 10], hidden=32, channels=[8, 8, 8], batch_size=8, replay_capacity=512,
    steps_per_call=1, ingest_block=16, target_sync_freq=8, num_actions=6,
)

# At hidden 64, 40 tokens and batch 8 on the CPU, as the solar cell's toy: eight
# rows at 64 wide average a gradient's bfloat16 rounding little and a router's
# choice flips under it, so these limits are this test's alone, its seeds are
# fixed, and the mechanism's flags are read with the program in float32 (the
# last test).  Read while writing this, seeds 2**31 + 9 and 2**31 + 77:
# program 0.113-0.140 / 0.092-0.094 / 0.174-0.175; gather_one_row_on
# 0.861-1.065 / 0.775-0.896 / 0.281-0.313, fp8_activations' median
# 0.275-0.371, bf16_held's update 0.480-0.497.
TOY_LIMITS = {"fused_priority_rel": 0.2, "fused_priority_median_rel": 0.15,
              "fused_update_rel": 0.3}

_OP = "jit(fused)/while/body/{}(stage:forward){}/LingHybridQ/"
_F, _B = _OP.format("jvp", ""), _OP.format("transpose(jvp", ")")
HLO = f"""HloModule jit_fused, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  ROOT %neg.1 = f32[4]{{0}} negate(%p), metadata={{op_name="jit(fused)/stage:sample/neg"}}
}}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {{
  %t = (s32[], f32[4]{{0}}) parameter(0)
  %x = f32[4]{{0}} get-tuple-element(%t), index=1
  %fusion.17 = f32[4]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layer_0/torso:mixer/linear_attention/torso:delta_scan/while/body/dot_general"}}
  %fusion.18 = f32[4]{{0}} fusion(%fusion.17), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layer_0/torso:dense_ffn/dense/dot_general"}}
  %constant.19 = s32[4]{{0}} constant({{0, 1, 2, 3}}), metadata={{op_name="{_F}layer_4/torso:mixer/latent_attention/torso:attn_latent/pallas_call"}}
  %attn_fwd.20 = f32[4]{{0}} custom-call(%constant.19, %fusion.18), custom_call_target="tpu_custom_call", operand_layout_constraints={{f32[4]{{0}}}}
  %fusion.21 = f32[4]{{0}} fusion(%attn_fwd.20), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layer_4/torso:mixer/latent_attention/mul"}}
  %fusion.22 = f32[4]{{0}} fusion(%fusion.21), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_5_6/torso:mixer/linear_attention/dot_general"}}
  %fusion.23 = f32[4]{{0}} fusion(%fusion.22), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_5_6/torso:mixer/linear_attention/torso:delta_scan/while/body/cumsum"}}
  %fusion.24 = f32[4]{{0}} fusion(%fusion.23), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{_B}layer_4/torso:mixer/latent_attention/torso:attn_latent/reduce_sum"}}
  %fusion.25 = f32[4]{{0}} fusion(%fusion.24), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_5_6/torso:router/moe/top_k"}}
  %fusion.26 = f32[4]{{0}} fusion(%fusion.25), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_5_6/moe/torso:experts/ragged_dot"}}
  %fusion.27 = f32[4]{{0}} fusion(%fusion.26), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layers_5_6/torso:shared_expert/shared_expert/dot_general"}}
  %fusion.28 = f32[4]{{0}} fusion(%fusion.27), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_B}layers_5_6/torso:mixer/linear_attention/torso:delta_scan/while/body/transpose(jvp(dot_general))"}}
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
OPS = [("fusion.9", 0, 50), ("while.13", 50, 900), ("fusion.17", 55, 45), ("fusion.18", 100, 50),
       ("attn_fwd.20", 150, 20), ("fusion.21", 170, 40), ("fusion.22", 210, 80),
       ("fusion.23", 290, 30), ("fusion.24", 320, 10), ("fusion.25", 330, 170),
       ("fusion.26", 500, 100), ("fusion.27", 600, 40), ("fusion.28", 640, 160),
       ("fusion.31", 820, 100)]
WANT = {"delta_scan": 45 + 30 + 160, "mixer": 40 + 80, "attn_latent": 20 + 10, "shared_expert": 40,
        "router": 170, "experts": 100, "dense_ffn": 50}


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
    cfg = mf.load_json(os.path.join(mf.HERE, "configs", "ling3_q_l7.json"))
    base = dict(trace=_trace(), fused_program="jit_fused", trace_reduce=tr, config=cfg,
                counters={"held_pairs_per_step": 56448.0, "load_max_per_step": 30.0,
                          "load_mean_per_step": 20.0,
                          "attention_blocks_visited_latent_per_step": 3 * 8 * 8 * 28.0,
                          "attention_blocks_total_latent_per_step": 3 * 8 * 8 * 52.0},
                end_to_end={"learn_samples_per_s": 10.0},
                peaks=json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"])
    return types.SimpleNamespace(**dict(base, **over))


def test_the_eight_times_add_up_to_the_programs_time(monkeypatch):
    import ops_count_ling3_q as ops

    monkeypatch.setattr(st, "program_texts", lambda name: ["HloModule unrelated\n", HLO])
    r = _readings()
    assert r.config["parts"] == PARTS and r.config["parts_scope"] == "torso:attn_latent"
    assert r.config["parts_prefix"] == "latent"
    table = pt.table(r)
    assert {k: v for k, v in table.items() if k != "rest"} == pytest.approx(WANT)
    # the gather, the optimizer, the while's own time and the time with no op,
    # and the ingest program's 80 us a call
    assert table["rest"] == pytest.approx(50 + 100 + 55 + 50 + 80)
    fused_us, runs = tr.module_seconds(r.trace, "jit_fused", *tr.span_window(r.trace))
    assert runs == 2 and sum(table.values()) == pytest.approx(fused_us / 2 * 1e6 + 80)
    cell = mf.Cell(mf.load_manifest(), CELL)
    mine = [m["name"] for m in cell.per_layer() if m["name"].startswith("latent.")]
    got = {n: cell.reader(n)(r) for n in mine}
    steps = [n for n in got if n.endswith("_step_us")]
    assert len(mine) == 10 and len(steps) == 8
    assert sum(got[n] for n in steps) == pytest.approx(sum(table.values()))
    assert {n[len("latent."):-len("_step_us")] for n in steps} == set(PARTS) | {"rest"}
    assert got["latent.delta_scan_roofline"] == pytest.approx(
        ops.delta_floor_s(r.config, r.peaks)[0] / (WANT["delta_scan"] * 1e-6) * 100)
    assert got["latent.attn_latent_roofline"] == pytest.approx(
        ops.attention_floor_s(r.config, r.peaks, "latent")[0] / (WANT["attn_latent"] * 1e-6) * 100)
    # the accepted readers this cell is appended to read it by the configuration's names
    assert cell.reader("torso.mfu_pct")(r) == pytest.approx(
        ops.flops_per_sample(r.config, 56448.0) * 10.0 / 197e12 * 100)
    assert 10 < cell.reader("torso.mfu_pct")(r) < 40
    assert cell.reader("moe.held_pairs_per_step")(r) == 56448.0
    assert cell.reader("moe.load_max_over_mean")(r) == pytest.approx(1.5)
    assert cell.reader("blocks.attn_blocks_visited_pct")(r) == pytest.approx(28 / 52 * 100)


def test_a_program_without_the_scope_gives_no_metric(monkeypatch):
    """The parent's program has no ``torso:attn_latent``: every new reader
    returns nothing and raises nothing."""
    monkeypatch.setattr(st, "program_texts", lambda name: [HLO.replace("torso:attn_latent", "torso:x")])
    cell = mf.Cell(mf.load_manifest(), CELL)
    for m in cell.per_layer():
        if m["name"].startswith("latent."):
            assert cell.reader(m["name"])(_readings()) is None, m["name"]


def test_the_count_is_the_hand_count():
    """ISSUE 42's table of parameters, and the latent layer's floor against a
    count by hand at a small shape."""
    import ops_count_ling3_q as ops
    import reference.ling3_q as ref

    cfg = mf.load_json(os.path.join(mf.HERE, "configs", "ling3_q_l7.json"))
    held = cfg["experts_held"][1]
    assert ops.mixer_param_count(cfg, "linear_attention") == (
        6 * 2560 * 1024 + 3 * 1024 * 4 + 2560 * 8 + 8 + 1024 + 128) == 15_762_568
    assert ops.mixer_param_count(cfg, "latent_attention") == (
        2560 * 1536 + 2560 * 576 + 512 * 2048 + 2560 * 8 + 1024 * 2560 + 512) == 9_097_728
    assert ops.expert_layer_param_count(cfg) == (
        2560 * 512 + 512 + 3 * 2560 * 768 + held * 3 * 2560 * 768)
    assert ops.param_count(cfg) == ref.param_count(cfg) == {16: 763_253_219, 8: 480_138_723}[held]
    assert ops.tokens_per_sample(cfg) == 1568 and ops.pairs_in_mask(cfg) == 1_230_096
    assert ops.pairs_in_chunks(cfg) == 24 * (64 * 65 // 2) + 32 * 33 // 2
    assert (ops.layers_of(cfg, "linear_attention"), ops.layers_of(cfg, "latent_attention"),
            ops.layers_of(cfg, "moe"), ops.layers_of(cfg, "dense")) == (6, 1, 6, 1)
    assert ops.expected_pairs_per_step(cfg) == pytest.approx(3 * 8 * 1568 * 8 * held / 512 * 6)
    # the delta rule's count is the other family's at 8 heads and 6 layers
    import ops_count_solar2_q as solar

    like = dict(cfg, gqa_layers=[], layers_held=list(range(6)), kda_chunk_size=64,
                linear_attn_config=dict(num_heads=8, head_dim=128, short_conv_kernel_size=4))
    assert ops.delta_macs_per_sample(cfg) == solar.delta_macs_per_sample(like)
    peaks = json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"]
    assert ops.delta_floor_s(cfg, peaks) == solar.delta_floor_s(like, peaks)
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
    # the share cannot pass 100%: the kernels read at least the operands the floor counts
    assert ops.attention_floor_s(cfg, peaks)[1] == "compute"


def test_the_manifests_new_entries():
    m = mf.load_manifest()
    cell = mf.Cell(m, CELL)
    assert cell.chips == 1 and cell.traffic_name == "learner_feed_collected"
    assert cell.config["network"] == "ling_hybrid" and cell.config["reference"] == "ling3_q"
    assert cell.config["ops_count"] == "ops_count_ling3_q"
    entry = [c for c in m["configs"] if c["name"] == "ling3_q_l7"][0]
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts", "num_attention_heads",
        "num_key_value_heads", "replay_capacity"]
    assert entry["source"] == cell.config["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json")
    mine = [x for x in m["per_layer"] if x["name"].startswith("latent.")]
    assert len(mine) == 10 and all(x["workloads"] == [CELL] and x["layer"] == "learner"
                                   and x["moves"] == "learn_samples_per_s"
                                   and x["source"] == "device_trace" for x in mine)
    listed = {x["name"] for x in m["per_layer"] if CELL in x.get("workloads", ())}
    assert listed - {x["name"] for x in mine} == SHARED_LISTS
    reported = {x["name"] for x in cell.per_layer()}
    assert {"ingest.ms_per_call", "fused.us_per_step", "device.idle_pct",
            "device.peak_hbm_gb"} <= reported and "hybrid.mfu_pct" not in reported
    assert not any(n.startswith(("linear.", "blocks.attn_full", "hybrid.")) for n in reported)
    # the published widths, uncut, and the catalog's numbers under their keys
    c = cell.config
    assert (c["hidden_size"], c["head_dim"], c["moe_intermediate_size"], c["intermediate_size"],
            c["moe_shared_expert_intermediate_size"], c["num_experts_per_tok"],
            c["num_shared_experts"], c["router_outputs"], c["n_group"], c["topk_group"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["short_conv_kernel_size"], c["kda_lower_bound"], c["routed_scaling_factor"],
            c["rope_theta"], c["layer_group_size"], c["vocab_size"]) == (
                2560, 128, 768, 6144, 768, 8, 1, 512, 8, 4, 512, 128, 64, 128, 4, -5, 2.5,
                6000000, 6, 157184)
    assert c["published"] == {"num_hidden_layers": 42, "first_k_dense_replace": 2,
                              "num_experts": 512, "num_attention_heads": 32,
                              "num_key_value_heads": 32}
    held = c["experts_held"][1]
    assert (c["num_hidden_layers"], c["first_k_dense_replace"], c["num_experts"],
            c["num_attention_heads"], c["num_key_value_heads"]) == (7, 1, held, 8, 8)
    assert (c["layers_held"], c["experts_held"], c["heads_held"]) == (
        [1, 2, 3, 4, 5, 6, 7], [0, held], [0, 8]) and held in (8, 16)
    kinds = [c["layer_types"][i] for i in c["layers_held"]]
    assert kinds == ["linear_attention"] * 4 + ["latent_attention"] + ["linear_attention"] * 2
    assert len(c["layer_types"]) == 42 and c["layer_types"].count("latent_attention") == 7
    assert all(c[name][i] == 0 for name in ("expert_swiglu_limit_list",
                                            "share_expert_swiglu_limit_list") for i in c["layers_held"])
    assert len(c["expert_swiglu_limit_list"]) == 42 and c["share_expert_swiglu_limit_list"][34] == 5
    assert set(c["reduced_why"]) == set(c["reduced"]) and "multi_token_prediction" in c["departures"]
    assert {"kda_gate", "use_qk_norm", "router", "swiglu_limits", "initialisation"} <= set(c["assumed"])
    # the catalog's numbers, every one under its key unless the cut lists it
    cut = set(c["reduced"])
    for key, value in {"kda_lower_bound": -5, "max_window_layers": 20, "mtp_loss_scaling_factor": 0,
                       "num_nextn_predict_layers": 1, "partial_rotary_factor": 0.5,
                       "qk_head_dim": 192, "rotary_dim": 64, "max_position_embeddings": 262144,
                       "rms_norm_eps": 1e-06, "num_kv_heads_for_linear_attn": 0,
                       "group_norm_size": 1}.items():
        assert c[key] == value and key not in cut, key
    # the order the contract asks for, held so that a later cell appended after this one breaks
    # nothing: this PR's entries follow the solar cell's, the ten metrics stand together
    configs, cells = [x["name"] for x in m["configs"]], [x["name"] for x in m["workloads"]]
    assert configs.index("solar2_q_ep40") < configs.index("ling3_q_l7")
    assert cells.index("solar2_q_ep40.learner") < cells.index(CELL)
    assert all(x["workloads"].index("solar2_q_ep40.learner") < x["workloads"].index(CELL)
               for x in m["per_layer"] if x["name"] in SHARED_LISTS)
    names = [x["name"] for x in m["per_layer"]]
    first = names.index("latent.delta_scan_step_us")
    assert names.index("linear.attn_full_roofline") < first and names[first:first + 10] == [
        "latent." + n for n in ("delta_scan_step_us", "mixer_step_us", "attn_latent_step_us",
                                "shared_expert_step_us", "router_step_us", "experts_step_us",
                                "dense_ffn_step_us", "rest_step_us", "delta_scan_roofline",
                                "attn_latent_roofline")]
    limits = mf.load_json(os.path.join(mf.HERE, "limits", "ling3_q_l7.json"))
    assert set(limits) == set(TOY_LIMITS) and all(0 < v["sound_max"] < v["limit"] for v in limits.values())
    # the flipped number admits the proven flips of a double-Q argmax (the largest read 0.4937) and
    # lies, as test_benchmark_manifest.py holds every limit, under its control's smallest reading
    assert all(v["limit"] < v["control_min"] for v in limits.values())
    flipped = limits["fused_priority_rel"]
    assert flipped["reading_max"] == 0.4937 and flipped["reading_max"] * 1.8 < flipped["limit"] < 0.9143
    # and says of itself that it has no upper reading: its sound_max leaves the flips out
    assert flipped["upper_reading"] is None and flipped["sound_max"] < flipped["reading_max"]
    assert 3 * flipped["reading_max"] > flipped["control_min"] and "NOT the largest" in flipped["sound_max_is"]
    assert not any("reading_max" in v for k, v in limits.items() if k != "fused_priority_rel")
    assert limits["fused_update_rel"]["control"] == "bf16_held" and (
        limits["fused_update_rel"]["control_min"] >= 1.5 * limits["fused_update_rel"]["limit"])


def test_what_the_solar_cells_two_pinned_tests_hold_beside_their_pins(monkeypatch):
    """``test_benchmark_solar_cell.test_the_manifests_new_entries`` and
    ``test_what_the_two_pinned_tests_hold_beside_their_pins`` pin the manifest
    to the PR that wrote them (the solar cell in the last place of every list
    it is on, its configuration, cell and nine metrics in the manifest's last
    places), which an appended cell breaks and this PR may not edit
    (``tests/conftest.py`` marks them expected to fail, with the reason).
    What they hold beside the pins is held here."""
    import test_benchmark_granite_cell as granite
    import test_benchmark_solar_cell as solar

    m = mf.load_manifest()
    cell = mf.Cell(m, solar.CELL)
    assert cell.chips == 1 and cell.traffic_name == "learner_feed_collected"
    assert cell.config["network"] == "solar_open2" and cell.config["reference"] == "solar2_q"
    entry = [c for c in m["configs"] if c["name"] == "solar2_q_ep40"][0]
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "num_attention_heads", "num_key_value_heads",
        "linear_attn_config", "replay_capacity"]
    mine = [x for x in m["per_layer"] if x["name"].startswith("linear.")]
    assert len(mine) == 9 and all(x["workloads"] == [solar.CELL] and x["layer"] == "learner"
                                  and x["moves"] == "learn_samples_per_s"
                                  and x["source"] == "device_trace" for x in mine)
    listed = {x["name"] for x in m["per_layer"] if solar.CELL in x.get("workloads", ())}
    assert listed - {x["name"] for x in mine} == SHARED_LISTS
    # the solar cell stands before this one on every list both are on
    assert all(x["workloads"].index(solar.CELL) < x["workloads"].index(CELL)
               for x in m["per_layer"] if x["name"] in SHARED_LISTS)
    reported = {x["name"] for x in cell.per_layer()}
    assert {"ingest.ms_per_call", "fused.us_per_step", "device.idle_pct",
            "device.peak_hbm_gb"} <= reported and "hybrid.mfu_pct" not in reported
    assert not any(n.startswith("latent.") for n in reported)
    c = cell.config
    assert (c["hidden_size"], c["head_dim"], c["moe_intermediate_size"], c["intermediate_size"],
            c["num_experts_per_tok"], c["n_shared_experts"], c["router_outputs"]) == (
                4096, 128, 1280, 10240, 8, 1, 320)
    assert c["published"] == {"num_hidden_layers": 48, "n_routed_experts": 320,
                              "num_attention_heads": 64, "num_key_value_heads": 8,
                              "linear_attn_config": {"num_heads": 64}}
    assert (c["layers_held"], c["experts_held"], c["heads_held"]) == ([0, 1, 2, 3], [0, 8], [0, 16])
    assert set(c["reduced_why"]) == set(c["reduced"])
    # the second pinned test, what it pinned of the order held relative: solar's nine metrics
    # stand together after granite's eight, its configuration and cell after granite's
    names = [x["name"] for x in m["per_layer"]]
    linear, hybrid = names.index("linear.delta_scan_step_us"), names.index("hybrid.ssm_scan_step_us")
    assert names[linear:linear + 9] == [
        "linear." + n for n in ("delta_scan_step_us", "mixer_step_us", "attn_full_step_us",
                                "shared_expert_step_us", "router_step_us", "experts_step_us",
                                "rest_step_us", "delta_scan_roofline", "attn_full_roofline")]
    assert hybrid + 8 == linear
    configs, cells = [x["name"] for x in m["configs"]], [x["name"] for x in m["workloads"]]
    assert configs.index("granite4h_q_l10") < configs.index("solar2_q_ep40")
    assert cells.index(granite.CELL) < cells.index(solar.CELL)
    # and everything else it holds, as it holds it: the laguna cell's readers on that cell's own
    # synthetic text and trace (the table, the eight times that add up, the two attention
    # rooflines, the experts' roofline, the whole step's share, the routing totals, the blocks
    # visited), the lists the expert cells share, and granite's entries wherever they now stand
    import blocks_times as bt
    import ops_count_laguna_q as laguna_ops
    import test_benchmark_laguna_cell as laguna

    monkeypatch.setattr(st, "program_texts", lambda name: ["HloModule unrelated\n", laguna.HLO])
    r = laguna._readings()
    table = bt.table(r)
    assert {k: v for k, v in table.items() if k != "rest"} == pytest.approx(laguna.WANT)
    assert table["rest"] == pytest.approx(50 + 100 + 40 + 50 + 80)
    fused_us, runs = tr.module_seconds(r.trace, "jit_fused", *tr.span_window(r.trace))
    assert runs == 2 and sum(table.values()) == pytest.approx(fused_us / 2 * 1e6 + 80)
    cell = mf.Cell(m, "laguna_q_ep32.learner")
    mine = [x["name"] for x in m["per_layer"] if x["name"].startswith("blocks.")]
    shared = ["torso.experts_roofline", "torso.mfu_pct", "moe.held_pairs_per_step",
              "moe.load_max_over_mean"]
    assert len(mine) == 11 and all(x["workloads"][:2] == ["lfm2moe_q_ep8.learner", cell.name]
                                   for x in m["per_layer"] if x["name"] in shared)
    assert all(x["workloads"][0] == cell.name for x in m["per_layer"] if x["name"] in mine)
    got = {n: cell.reader(n)(r) for n in mine + shared}
    steps = [n for n in got if n.endswith("_step_us")]
    assert len(steps) == 8 and sum(got[n] for n in steps) == pytest.approx(sum(table.values()))
    for kind in ("full", "window"):
        floor = laguna_ops.attention_floor_s(r.config, r.peaks, kind)[0]
        assert got[f"blocks.attn_{kind}_roofline"] == pytest.approx(
            floor / (laguna.WANT["attn_" + kind] * 1e-6) * 100)
    assert got["torso.experts_roofline"] == pytest.approx(
        laguna_ops.expert_floor_s(r.config, r.peaks, 47040.0)[0] / 50e-6 * 100)
    assert got["torso.mfu_pct"] == pytest.approx(
        laguna_ops.flops_per_sample(r.config, 47040.0) * 9.0 / 197e12 * 100)
    assert got["moe.held_pairs_per_step"] == 47040.0
    assert got["moe.load_max_over_mean"] == pytest.approx(1.5)
    assert got["blocks.attn_blocks_visited_pct"] == pytest.approx(
        (2 * 48 * 3 + 3 * 72 * 7) / (2 * 48 * 4 + 3 * 72 * 16) * 100)
    cell = mf.Cell(m, granite.CELL)
    entry = [c for c in m["configs"] if c["name"] == "granite4h_q_l10"][0]
    assert entry["reduced"] == cell.config["reduced"] == ["num_hidden_layers", "replay_capacity"]
    hybrid = [x for x in m["per_layer"] if x["name"].startswith("hybrid.")]
    assert len(hybrid) == 8 and all(x["workloads"] == [granite.CELL] for x in hybrid)
    listed = [x["name"] for x in m["per_layer"] if granite.CELL in x.get("workloads", ())]
    assert len(listed) == 16 and not any(n.startswith(("blocks.", "torso.", "moe.")) for n in listed)


def _toy_config(**over):
    return dict(mf.load_json(os.path.join(mf.HERE, "configs", "ling3_q_l7.json")), **SMALL, **over)


def _toy_traffic():
    traffic = mf.load_json(os.path.join(mf.HERE, "traffic", "learner_feed_collected.json"))
    traffic["check"] = dict(traffic["check"], ring_rows_per_chip=256, ingest_rows_per_chip=32)
    return traffic


DRIVE = r"""
import json, sys, types
sys.path[:0] = [sys.argv[1] + "/benchmark", sys.argv[1]]
import jax
import manifest as mf, run
run.live_peak_bytes = lambda devs: 0     # the CPU backend reports no memory_stats
cell = mf.Cell(mf.load_manifest(sys.argv[1]), "toy_ling.learner", root=sys.argv[1],
               bench_dir=sys.argv[1] + "/benchmark")
args = types.SimpleNamespace(seed=2**31 + 77, seconds=0.5, trace=0)
print(json.dumps(run.measure(cell, args, jax.devices(), peaks=None)))
"""


@pytest.mark.slow    # as the solar cell's toy run (150-240 s on six workers); the program against the
# reference at the toy size is test_benchmark_ling_reference.py's, the cell itself runs on the chip
def test_toy_ling_cell_runs_and_is_correct(tmp_path):
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(mf.HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    toy = _toy_config()
    with open(os.path.join(root, "benchmark", "configs", "toy_ling.json"), "w") as f:
        json.dump(toy, f)
    with open(os.path.join(root, "benchmark", "limits", "toy_ling.json"), "w") as f:
        json.dump({name: {"limit": limit} for name, limit in TOY_LIMITS.items()}, f)
    with open(os.path.join(root, "benchmark", "traffic", "toy_collected.json"), "w") as f:
        json.dump(_toy_traffic(), f)
    m = mf.load_manifest(root)
    m["configs"].append({"name": "toy_ling", "source": "test",
                         "file": "benchmark/configs/toy_ling.json",
                         "reduced": toy["reduced"], "why": "test"})
    m["workloads"].append({"name": "toy_ling.learner", "config": "toy_ling",
                           "traffic": "toy_collected", "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    p = subprocess.run([sys.executable, "-c", DRIVE, root], env=ENV, cwd=root,
                       capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3, \
        p.stdout[-3000:]
    assert set(result["metrics"]) == {"learn_samples_per_s", "setup_s"}
    for what in ("step counter", "compilations inside the window", "ring_rows_differing",
                 "fused_priority_rel", "fused_priority_median_rel", "fused_update_rel"):
        assert f"compare {what} = " in p.stdout, what
    counters = p.stdout.split("counters ", 1)[1].splitlines()[0]
    assert "'attention_blocks_visited_latent_per_step'" in counters
    assert "'held_pairs_per_step'" in counters


@pytest.mark.slow    # 140 s alone; that each flag moves Q, and that the lower precisions move the three
# numbers, is test_benchmark_ling_reference.py's in seconds; on the chip the flags are check_latent_controls'
def test_the_comparison_sees_all_four_mechanism_flags():
    """The comparison's two calls at the toy size with the program computing
    in float32, so that its own rounding is out of the way: the program reads
    far under every limit; the reference that forgot its groups, lost its
    shared key, took the other family's gate or lost its carry, each in the
    program's place, reads over five times the program's on every number.
    Read as ``check_latent_controls.py`` reads them on the chip: each flag as
    one more of the driver's controls."""
    import check_flag_control
    import check_latent_controls
    import reference.ling3_q as ref

    assert check_latent_controls.FLAGS == ref.FLAGS
    before_flags = check_flag_control.FLAGS
    cfg = _toy_config()
    cfg["precision"] = dict(cfg["precision"], compute="float32", target_params="float32",
                            second_moment="float32")
    traffic, beta = _toy_traffic(), float(_toy_traffic()["beta"])
    drv = mf.load_module(os.path.join(mf.HERE, "drivers", "learner_feed_collected.py"),
                         "bench_driver_learner_feed_collected")
    inputs, shots = drv.check_shots(cfg, traffic, 2**31 + 9)
    counts, got, reference = drv.base.program_numbers(cfg, beta, inputs, shots)
    assert counts == dict.fromkeys(counts, 0) and shots["routing"]["held_pairs"] > 0
    assert all(got[name] <= 0.1 * limit for name, limit in TOY_LIMITS.items()), got
    before = dict(drv.base.CONTROLS)
    check_flag_control.FLAGS = check_latent_controls.FLAGS
    try:
        with check_flag_control.flags_as_controls(drv.base, list(ref.FLAGS)) as base:
            assert list(base.CONTROLS) == list(ref.FLAGS)
            for flag in ref.FLAGS:
                numbers = base.control_numbers(cfg, beta, inputs, shots, reference,
                                               *base.CONTROLS[flag])
                assert all(numbers[name] > 5 * got[name] for name in got), (flag, numbers, got)
    finally:
        check_flag_control.FLAGS = before_flags
    assert drv.base.CONTROLS == before
