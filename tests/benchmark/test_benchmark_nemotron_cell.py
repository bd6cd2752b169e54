"""The cell ``nemotron3s_q_ep32.learner``: the ``latmoe.*`` readers on a
hand-made program text and trace of this family (seven parts and ``rest`` that
add up to the program's time, two roofline shares), the operation count against
a count by hand, the manifest's appended entries with the order held relative,
the configuration against the catalog's row, the limits file against the
readings it states, what the Kanana cell's pinned test holds beside its pin,
and a copy of the configuration at small widths for the reference's tests
(``test_benchmark_nemotron_reference.py``)."""
import json
import os
import types

import pytest

import manifest as mf
import parts_times as pt
import stage_times as st
import trace_reduce as tr
from trace_reduce import DeviceTrace, Event, Trace

CELL = "nemotron3s_q_ep32.learner"
CONFIG = "nemotron3s_q_ep32"
PARTS = ["ssm_scan", "mixer", "attn_full", "latent_proj", "shared_expert", "router", "experts"]
STAGE_LISTS = {
    "replay.ingest_us_per_step", "replay.sample_us_per_step", "replay.gather_us_per_step",
    "replay.restamp_us_per_step", "learner.forward_us_per_step", "learner.backward_us_per_step",
    "learner.optimizer_unfused_us_per_step", "fused.other_us_per_step"}
SHARED_LISTS = STAGE_LISTS | {
    "torso.mfu_pct", "torso.experts_roofline", "moe.held_pairs_per_step", "moe.load_max_over_mean",
    "blocks.attn_blocks_visited_pct"}
LATMOE_LISTS = {"latmoe." + n for n in (
    *(p + "_step_us" for p in PARTS), "rest_step_us", "ssm_scan_roofline", "attn_full_roofline")}

# seven of twelve layers (M E M E M * E), half the Mamba-2 heads (two groups of four), query heads and
# shared columns, four of sixteen experts: ``tests/torso_contract.NEMOTRON``'s block
SMALL = dict(
    hidden_size=64, intermediate_size=48, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=64, n_shared_experts=1,
    hybrid_override_pattern="ME*EMEMEM*EM", num_hidden_layers=7, layers_held=[4, 5, 6, 7, 8, 9, 10],
    published=dict(num_hidden_layers=12, n_routed_experts=16, mamba_num_heads=8,
                   num_attention_heads=4, num_key_value_heads=2),
    mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16, conv_kernel=4, chunk_size=16,
    n_groups=4, expand=2, num_attention_heads=2, num_key_value_heads=1, head_dim=16,
    heads_held=[0, 2], mamba_heads_held=[0, 4], shared_expert_held=[0, 32],
    n_routed_experts=4, router_outputs=16, experts_held=[4, 8], num_experts_per_tok=3,
    obs_shape=[44, 44, 10], hidden=32, channels=[8, 8, 8], batch_size=8, replay_capacity=512,
    steps_per_call=1, ingest_block=16, target_sync_freq=8, num_actions=6,
)

# At hidden 64, 40 tokens and batch 8 on the CPU, as the other cells' toys: the
# limits are this test's alone and its seed is fixed.  Read while writing this,
# seed 2**31 + 9 (priority / median / update): the program 0.054 / 0.043 /
# 0.056; gather_one_row_on 1.140 / 0.861 / 0.181, fp8_activations' median 0.150,
# bf16_held's update 0.399.
TOY_LIMITS = {"fused_priority_rel": 0.3, "fused_priority_median_rel": 0.1,
              "fused_update_rel": 0.15}

_OP = "jit(fused)/while/body/{}(stage:forward){}/NemotronHQ/"
_F, _B = _OP.format("jvp", ""), _OP.format("transpose(jvp", ")")
_RUN = "layers_0_3/while/body/"
HLO = f"""HloModule jit_fused, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  ROOT %neg.1 = f32[4]{{0}} negate(%p), metadata={{op_name="jit(fused)/stage:sample/neg"}}
}}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {{
  %t = (s32[], f32[4]{{0}}) parameter(0)
  %x = f32[4]{{0}} get-tuple-element(%t), index=1
  %fusion.17 = f32[4]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}{_RUN}torso:mixer/mamba/dot_general"}}
  %fusion.18 = f32[4]{{0}} fusion(%fusion.17), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}{_RUN}torso:mixer/mamba/torso:ssm_scan/while/body/vmap(dot_general)"}}
  %fusion.19 = f32[4]{{0}} fusion(%fusion.18), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}{_RUN}torso:router/moe/reduce_max"}}
  %fusion.20 = f32[4]{{0}} fusion(%fusion.19), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}{_RUN}moe/torso:latent_proj/dot_general"}}
  %fusion.21 = f32[4]{{0}} fusion(%fusion.20), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}{_RUN}moe/torso:experts/ragged_dot"}}
  %fusion.22 = f32[4]{{0}} fusion(%fusion.21), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}{_RUN}torso:shared_expert/shared_expert/dot_general"}}
  %fusion.23 = f32[4]{{0}} fusion(%fusion.22), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layer_4/torso:mixer/mamba/torso:ssm_scan/while/body/vmap(exp)"}}
  %fusion.24 = f32[4]{{0}} fusion(%fusion.23), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_F}layer_5/torso:mixer/attention/mul"}}
  %constant.25 = s32[4]{{0}} constant({{0, 1, 2, 3}}), metadata={{op_name="{_F}layer_5/torso:mixer/attention/torso:attn_full/pallas_call"}}
  %attn_fwd.26 = f32[4]{{0}} custom-call(%constant.25, %fusion.24), custom_call_target="tpu_custom_call", operand_layout_constraints={{f32[4]{{0}}}}
  %fusion.27 = f32[4]{{0}} fusion(%attn_fwd.26), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_B}layer_5/moe/torso:latent_proj/transpose(jvp(dot_general))"}}
  %fusion.28 = f32[4]{{0}} fusion(%fusion.27), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{_B}{_RUN}torso:mixer/mamba/torso:ssm_scan/while/body/pass:again/vmap(dot_general)"}}
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
OPS = [("fusion.9", 0, 50), ("while.13", 50, 900), ("fusion.17", 55, 45), ("fusion.18", 100, 70),
       ("fusion.19", 170, 130), ("fusion.20", 300, 30), ("fusion.21", 330, 100), ("fusion.22", 430, 40),
       ("fusion.23", 470, 20), ("fusion.24", 490, 10), ("attn_fwd.26", 500, 60), ("fusion.27", 560, 25),
       ("fusion.28", 600, 180), ("fusion.31", 820, 100)]
WANT = {"ssm_scan": 70 + 20 + 180, "mixer": 45 + 10, "attn_full": 60, "latent_proj": 30 + 25,
        "shared_expert": 40, "router": 130, "experts": 100}


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
                counters={"held_pairs_per_step": 129360.0, "load_max_per_step": 30.0,
                          "load_mean_per_step": 20.0,
                          "attention_blocks_visited_full_per_step": 3 * 8 * 8 * 28.0,
                          "attention_blocks_total_full_per_step": 3 * 8 * 8 * 52.0},
                end_to_end={"learn_samples_per_s": 12.0},
                peaks=json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"])
    return types.SimpleNamespace(**dict(base, **over))


def test_the_seven_parts_and_the_rest_add_up_to_the_programs_time(monkeypatch):
    import ops_count_nemotron3s_q as ops

    monkeypatch.setattr(st, "program_texts", lambda name: ["HloModule unrelated\n", HLO])
    r = _readings()
    assert r.config["parts"] == PARTS and r.config["parts_scope"] == "torso:ssm_scan"
    assert r.config["parts_prefix"] == "latmoe"
    table = pt.table(r)
    assert {k: v for k, v in table.items() if k != "rest"} == pytest.approx(WANT)
    # the gather, the optimizer, the while's own time and the time with no op, the ingest's 80 us
    fused_us, runs = tr.module_seconds(r.trace, "jit_fused", *tr.span_window(r.trace))
    assert runs == 2 and sum(table.values()) == pytest.approx(fused_us / 2 * 1e6 + 80)
    assert table["rest"] == pytest.approx(1000 + 80 - sum(WANT.values()))
    cell = mf.Cell(mf.load_manifest(), CELL)
    mine = [m["name"] for m in cell.per_layer() if m["name"].startswith("latmoe.")]
    assert set(mine) == LATMOE_LISTS
    got = {n: cell.reader(n)(r) for n in mine}
    steps = [n for n in got if n.endswith("_step_us")]
    assert len(steps) == 8 and sum(got[n] for n in steps) == pytest.approx(sum(table.values()))
    assert {n[len("latmoe."):-len("_step_us")] for n in steps} == set(PARTS) | {"rest"}
    assert got["latmoe.ssm_scan_roofline"] == pytest.approx(
        ops.scan_floor_s(r.config, r.peaks)[0] / (WANT["ssm_scan"] * 1e-6) * 100)
    assert got["latmoe.attn_full_roofline"] == pytest.approx(
        ops.attention_floor_s(r.config, r.peaks, "full")[0] / (WANT["attn_full"] * 1e-6) * 100)
    # the accepted readers this cell is appended to read it by the configuration's names
    assert cell.reader("torso.mfu_pct")(r) == pytest.approx(
        ops.flops_per_sample(r.config, 129360.0) * 12.0 / 197e12 * 100)
    assert 20 < cell.reader("torso.mfu_pct")(r) < 40
    assert cell.reader("moe.held_pairs_per_step")(r) == 129360.0
    assert cell.reader("moe.load_max_over_mean")(r) == pytest.approx(1.5)
    assert cell.reader("blocks.attn_blocks_visited_pct")(r) == pytest.approx(28 / 52 * 100)


@pytest.mark.parametrize("name", sorted(LATMOE_LISTS))
def test_a_program_without_the_scope_gives_no_metric(monkeypatch, name):
    """The parent's program of this cell does not exist, and a program with no
    ``torso:ssm_scan`` gives no table: every reader returns nothing and raises
    nothing."""
    monkeypatch.setattr(st, "program_texts", lambda name: [HLO.replace("torso:ssm_scan", "torso:x")])
    assert mf.Cell(mf.load_manifest(), CELL).reader(name)(_readings()) is None


def test_the_count_is_the_hand_count():
    """ISSUE 59's table of parameters, and the floors against a count by hand."""
    import ops_count_nemotron3s_q as ops
    import reference.nemotron3s_q as ref

    cfg = _config()
    mamba = 4096 + 4096 * (2048 + 2560 + 32) + 2560 * 4 + 2560 + 3 * 32 + 2048 + 2048 * 4096
    attention = 4096 + 2 * 4096 * 1024 + 2 * 4096 * 128
    experts = (4096 + 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 1344 + 16 * 2 * 1024 * 2688)
    assert (mamba, attention, experts) == (27_413_088, 9_441_280, 109_580_800)
    assert [ops.layer_param_count(cfg, k) for k in ("mamba", "attention", "moe")] == [
        mamba, attention, experts]
    assert ops.param_count(cfg) == ref.param_count(cfg) == 698_953_875
    assert ops.tokens_per_sample(cfg) == 1568 and ops.pairs_in_mask(cfg) == 1_230_096
    assert [ops.layers_of(cfg, k) for k in ("mamba", "moe", "attention")] == [5, 5, 1]
    assert ops.mamba_sizes(cfg) == (2048, 256, 32) and ops.shared_columns(cfg) == 1344
    assert ops.expected_pairs_per_step(cfg) == pytest.approx(3 * 8 * 1568 * 22 * 16 / 512 * 5) == 129_360
    # 12 whole chunks of 128 and one of 32
    assert ops.pairs_in_chunks(cfg) == 12 * (128 * 129 // 2) + 32 * 33 // 2
    assert ops.scan_macs_per_sample(cfg) == 5 * (
        ops.pairs_in_chunks(cfg) * (2 * 128 + 2048) + 1568 * 2 * 2048 * 128)
    assert ops.attention_macs_per_sample(cfg) == 2 * 128 * 8 * 1_230_096
    assert ops.expert_macs_per_pair(cfg) == 2 * 1024 * 2688
    assert ops.macs_per_token(cfg) == dict(
        tokens=64 * 4096, mixer=5 * (4096 * (2 * 2048 + 2 * 256 + 32) + 2048 * 4096)
        + 2 * 4096 * 1024 + 2 * 4096 * 128, router=5 * 4096 * 512, latent_proj=5 * 2 * 4096 * 1024,
        shared_expert=5 * 2 * 4096 * 1344)
    peaks = json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"]
    assert ops.step_flops(cfg, 129_360.0) == pytest.approx(34.89e12, rel=1e-3)
    assert ops.step_floor_s(cfg, peaks, 129_360.0) == (pytest.approx(0.1771, rel=1e-3), "compute")
    # the scan at chunk 128 and heads of 64 is bound by its reads and writes, the attention
    # layer's eight heads on one key-value head by their products
    floor, bound = ops.scan_floor_s(cfg, peaks)
    a_pass = 5 * 1568 * ((2 * 2048 + 2 * 256) * 2 + 32 * 4)
    assert bound == "bandwidth" and floor == pytest.approx(5 * 8 * a_pass / 819e9)
    floor, bound = ops.attention_floor_s(cfg, peaks)
    assert bound == "compute" and floor == pytest.approx(
        5 * 2 * 2 * 128 * 8 * 1_230_096 * 8 / 197e12)
    slow = {"flops_per_s_bf16": 1e18, "hbm_bytes_per_s": 1e6}
    forward, backward = (2 * 8 + 2) * 1568 * 128 * 2, (4 * 8 + 4) * 1568 * 128 * 2
    assert ops.attention_floor_s(cfg, slow) == (pytest.approx(8 * (3 * forward + backward) / 1e6), "bandwidth")
    with pytest.raises(ValueError):
        ops.attention_floor_s(cfg, peaks, "window")
    # the experts' floor from the pairs really routed: FLOPs at even loads, the weights' reads at few
    assert ops.expert_floor_s(cfg, peaks, 129_360.0)[1] == "compute"
    assert ops.expert_floor_s(cfg, peaks, 1000.0)[1] == "bandwidth"


def test_the_manifests_appended_entries():
    m = mf.load_manifest()
    cell = mf.Cell(m, CELL)
    assert cell.chips == 1 and cell.traffic_name == "learner_feed_collected"
    assert cell.config["network"] == "nemotron_h" and cell.config["reference"] == "nemotron3s_q"
    assert cell.config["ops_count"] == "ops_count_nemotron3s_q"
    entry = [c for c in m["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "mamba_num_heads", "num_attention_heads",
        "num_key_value_heads", "replay_capacity"]
    assert entry["source"] == cell.config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/nemotron3s_q_ep32.json"
    assert len(entry["why"]) <= 200 and len(cell.workload["why"]) <= 200
    listed = {x["name"] for x in m["per_layer"] if CELL in x.get("workloads", ())}
    assert listed == SHARED_LISTS | LATMOE_LISTS
    reported = {x["name"] for x in cell.per_layer()}
    assert {"ingest.ms_per_call", "fused.us_per_step", "device.idle_pct", "device.peak_hbm_gb",
            "pass.bootstrap_step_us", "pass.forward_step_us", "pass.recompute_step_us",
            "pass.backward_step_us", "pass.walk_recompute_step_us",
            "pass.walk_backward_step_us"} <= reported
    assert not any(n.startswith(("linear.", "latent.", "blocks.attn_full", "hybrid.", "gdn."))
                   for n in reported)
    assert [x["name"] for x in cell.end_to_end()] == ["learn_samples_per_s", "setup_s"]
    for x in m["per_layer"]:
        if x["name"].startswith("latmoe."):
            assert x["workloads"] == [CELL] and x["layer"] == "learner", x["name"]
            assert x["unit"] == ("%" if x["name"].endswith("_roofline") else "us"), x["name"]
            assert os.path.isfile(os.path.join(mf.HERE, "layer_metrics", x["name"] + ".py"))
    # the order the contract asks for, held relative so that a later cell appended after this
    # one breaks nothing: this PR's entries follow the Kanana cell's
    configs, cells = [x["name"] for x in m["configs"]], [x["name"] for x in m["workloads"]]
    assert configs.index("kanana2_q_ep8") < configs.index(CONFIG)
    assert cells.index("kanana2_q_ep8.learner") < cells.index(CELL)
    names = [x["name"] for x in m["per_layer"]]
    assert max(names.index(n) for n in names if not n.startswith("latmoe.")) < min(
        names.index(n) for n in LATMOE_LISTS)
    for x in m["per_layer"]:
        if CELL in x.get("workloads", ()):
            others = [w for w in x["workloads"] if w != CELL]
            assert all(x["workloads"].index(CELL) > x["workloads"].index(w) for w in others), x["name"]
            assert x["moves"] == "learn_samples_per_s", x["name"]
    assert [w["chips"] for w in m["workloads"]].count(4) == 1


def test_the_configuration_is_the_catalogs_row_but_for_the_cut():
    """Every number of the published ``config.json`` under its key; the five
    keys the cut changes are in ``reduced`` and under ``published``; no width
    differs, the shared expert's among them."""
    c = _config()
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 4096,
        "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 22, "num_hidden_layers": 88, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
        "use_mamba_kernels": True, "vocab_size": 131072}
    cut = {"num_hidden_layers": 11, "n_routed_experts": 16, "mamba_num_heads": 32,
           "num_attention_heads": 8, "num_key_value_heads": 1}
    for key, value in published.items():
        assert c[key] == cut.get(key, value), key
        assert (key in c["reduced"]) == (key in cut), key
    assert c["published"] == {k: published[k] for k in cut}
    assert len(c["hybrid_override_pattern"]) == 88
    assert "".join(c["hybrid_override_pattern"][i] for i in c["layers_held"]) == "MEMEMEMEM*E"
    assert c["layers_held"] == list(range(27, 38)) and len(c["layer_types"]) == 88
    assert (c["experts_held"], c["router_outputs"], c["heads_held"], c["mamba_heads_held"],
            c["shared_expert_held"]) == ([0, 16], 512, [0, 8], [0, 32], [0, 1344])
    assert set(c["reduced_why"]) == set(c["reduced"]) == set(cut) | {"replay_capacity"}
    assert not any(key.endswith(("_dim", "_rank", "_size")) or key == "expand" for key in c["reduced"])
    assert {"no_positional_rule", "latent_moe", "shared_expert_share", "router", "expert_bias",
            "mamba_layer", "initialisation", "tokenisation", "timed_state"} <= set(c["assumed"])
    assert set(c["departures"]) == {"multi_token_prediction", "embedding_and_head", "bias_rule",
                                    "initialisation"}
    assert "TP4 x EP32" in c["deployment"] and "an eighth of the deployment's 4,312" in c["deployment"]
    assert (c["batch_size"], c["steps_per_call"], c["ingest_block"], c["chips"],
            c["replay_capacity"]) == (8, 1, 16, 1, 4096)
    kanana = mf.load_json(os.path.join(mf.HERE, "configs", "kanana2_q_ep8.json"))
    for key in ("channels", "hidden", "obs_shape", "num_actions", "n_step", "gamma", "replay_layout",
                "frame_ratio", "target_sync_freq", "optimizer", "learning_rate", "rmsprop_decay",
                "rmsprop_eps", "max_grad_norm", "loss", "priority_exponent", "is_exponent",
                "precision", "sample_ahead", "expert_bias_update_rate"):
        assert c[key] == kanana[key], key           # around the layers everything is Kanana's


def test_the_limits_lie_between_their_readings():
    """``limits/nemotron3s_q_ep32.json`` under the accepted manifest test's
    rule, and what it says of each control."""
    import reference.nemotron3s_q as ref

    limits = mf.load_json(os.path.join(mf.HERE, "limits", CONFIG + ".json"))
    assert set(limits) == set(TOY_LIMITS)
    for name, v in limits.items():
        assert 0 < v["sound_max"] < v["limit"] < v["control_min"], name
        assert 3 * v["sound_max"] <= v["control_min"], name
        assert v["control"] in ("bf16_held", "fp8_activations", "gather_one_row_on") + ref.FLAGS, name
        # the file says of every control, this reference's five flags among them, which number sees it
        assert set(v["seen_by"]) == set(ref.FLAGS) | {"bf16_held", "fp8_activations", "gather_one_row_on"}
        assert "TPU v5 lite" in v["readings"] and "PR 59" in v["readings"], name
    assert limits["fused_update_rel"]["control"] == "bf16_held"


def test_what_the_kanana_cells_pinned_test_holds_beside_its_pin(monkeypatch):
    """``test_benchmark_kanana_cell.test_the_manifests_appended_entries`` pins
    the manifest to ten configurations, ten cells and 69 per-layer metrics, and
    this PR appended to each (``tests/conftest.py`` marks it expected to fail,
    with the reason).  On the manifest with this PR's entries taken off again
    (the configuration, the cell, the ``latmoe.*`` metrics and the cell's name
    on the thirteen lists it was appended to) the test runs as it stands: what
    it holds beside that pin is held here, and nothing that was there moved."""
    import test_benchmark_kanana_cell as kanana

    load = mf.load_manifest

    def cut():
        m = load()
        m["configs"] = [c for c in m["configs"] if c["name"] != CONFIG]
        m["workloads"] = [w for w in m["workloads"] if w["name"] != CELL]
        m["per_layer"] = [x for x in m["per_layer"] if not x["name"].startswith("latmoe.")]
        for x in m["per_layer"]:
            if "workloads" in x:
                assert x["workloads"].count(CELL) <= 1 and (
                    CELL not in x["workloads"] or x["workloads"][-1] == CELL), x["name"]
                x["workloads"] = [w for w in x["workloads"] if w != CELL]
        return m

    assert len(load()["configs"]) == 11 and len(load()["workloads"]) == 11
    assert len(load()["per_layer"]) == 69 + len(LATMOE_LISTS)
    monkeypatch.setattr(mf, "load_manifest", cut)
    kanana.test_the_manifests_appended_entries()


def _toy_config(**over):
    cfg = dict(_config(), **SMALL, **over)
    kinds = {"M": "mamba", "E": "moe", "*": "attention"}
    return dict(cfg, layer_types=[kinds[c] for c in cfg["hybrid_override_pattern"]])


def _toy_traffic():
    traffic = mf.load_json(os.path.join(mf.HERE, "traffic", "learner_feed_collected.json"))
    traffic["check"] = dict(traffic["check"], ring_rows_per_chip=256, ingest_rows_per_chip=32)
    return traffic
