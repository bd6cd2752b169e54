"""The cell ``granite4h_q_l10.learner``: its ``hybrid.*`` readers on a
hand-made program text and trace, the manifest's new entries, and the cell at
a toy size on the CPU, where a copy of its configuration with small widths
runs through ``run.measure`` under the driver ``learner_feed_collected`` and
comes out correct, and the reference whose state is reset at every chunk
boundary does not."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import hybrid_times as ht
import manifest as mf
import stage_times as st
import trace_reduce as tr
from trace_reduce import DeviceTrace, Event, Trace

ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=mf.ROOT,
           XLA_FLAGS="--xla_force_host_platform_device_count=1")
CELL = "granite4h_q_l10.learner"

SMALL = dict(
    hidden_size=64, shared_intermediate_size=128, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, attention_multiplier=0.0625, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=8, mamba_chunk_size=16, obs_shape=[44, 44, 10], hidden=32, channels=[8, 8, 8],
    batch_size=8, replay_capacity=512, steps_per_call=1, ingest_block=16, target_sync_freq=8,
    num_actions=6,
)

# At hidden 64, 40 tokens and batch 8 on the CPU (read while writing this,
# seeds 2**31 + 9, 2**31 + 77 and 12345): program 0.007-0.024 / 0.007-0.012 /
# 0.18-0.38; fp8_activations 0.098-0.166 / 0.096-0.161 / 0.95-1.21, bf16_held's
# update 8.9-12.1, gather_one_row_on 0.81-1.11 / 0.48-0.82 / 1.31-1.47.  Eight
# rows at 64 wide average a gradient's bfloat16 rounding little, so these
# limits are this test's alone, and its seeds are fixed.
TOY_LIMITS = {"fused_priority_rel": 0.07, "fused_priority_median_rel": 0.035,
              "fused_update_rel": 0.8}

HLO = """HloModule jit_fused, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(fused)/stage:sample/neg"}
}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%t), index=1
  %fusion.20 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/GraniteHybridQ/layers_0_4/torso:mixer/mamba/dot_general"}
  %fusion.21 = f32[4]{0} fusion(%fusion.20), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/GraniteHybridQ/layers_0_4/torso:mixer/mamba/torso:ssm_scan/while/body/cumsum"}
  %ssm_chunk.22 = f32[4]{0} fusion(%fusion.21), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/GraniteHybridQ/layers_0_4/torso:mixer/mamba/torso:ssm_scan/while/body/dot_general"}
  %fusion.23 = f32[4]{0} fusion(%ssm_chunk.22), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/GraniteHybridQ/layers_0_4/torso:mixer/mamba/mul"}
  %fusion.24 = f32[4]{0} fusion(%fusion.23), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/GraniteHybridQ/layers_0_4/torso:dense_ffn/dense/dot_general"}
  %pad.25 = f32[4]{0} pad(%fusion.24), metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/GraniteHybridQ/layer_5/torso:mixer/attention/torso:attn_full/pad"}
  %splash_mha_dkv.26 = f32[4]{0} custom-call(%pad.25, %pad.25), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4]{0}}
  %fusion.27 = f32[4]{0} fusion(%splash_mha_dkv.26), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/transpose(jvp(stage:forward))/GraniteHybridQ/layer_5/torso:mixer/attention/dot_general"}
  %fusion.28 = f32[4]{0} fusion(%fusion.27), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/transpose(jvp(stage:forward))/GraniteHybridQ/layers_6_9/torso:mixer/mamba/torso:ssm_scan/while/body/transpose(jvp(dot_general))"}
  %fusion.31 = f32[4]{0} fusion(%fusion.28), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/stage:optimizer/sub"}
  %i = s32[] get-tuple-element(%t), index=0
  ROOT %out = (s32[], f32[4]{0}) tuple(%i, %fusion.31)
}

ENTRY %main.3 (ring: f32[4]) -> f32[4] {
  %ring = f32[4]{0} parameter(0), metadata={op_name="replay_state.rows"}
  %fusion.9 = f32[4]{0} fusion(%ring), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/stage:gather/gather"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[4]{0}) tuple(%zero, %fusion.9)
  %while.13 = (s32[], f32[4]{0}) while(%init), condition=%cond.4, body=%body.2, metadata={op_name="jit(fused)/while"}
  ROOT %res = f32[4]{0} get-tuple-element(%while.13), index=1
}
"""
# microseconds of each instruction in one run of the program (K = 1)
OPS = [("fusion.9", 0, 50), ("while.13", 50, 900), ("fusion.20", 60, 200), ("fusion.21", 260, 30),
       ("ssm_chunk.22", 290, 70), ("fusion.23", 360, 40), ("fusion.24", 400, 100),
       ("pad.25", 500, 10), ("splash_mha_dkv.26", 510, 120), ("fusion.27", 630, 80),
       ("fusion.28", 710, 110), ("fusion.31", 820, 100)]
WANT = {"ssm_scan": 30 + 70 + 110, "attn_full": 130, "mixer": 200 + 40 + 80, "dense_ffn": 100}


def _trace():
    """Two whole runs of 1,000 us (30 of them with no op), one cut by the
    window's start, and an ingest program of 80 us between them."""
    us = 1e-6
    starts = (-500, 1000, 2200)
    dev = DeviceTrace(
        ops=[Event(f"%{n} = f32[4]{{0}} fusion(%x)", (t0 + s) * us, (t0 + s + d) * us)
             for t0 in starts for n, s, d in OPS]
        + [Event("%add.1 = s32[] add(%a, %b)", 2050 * us, 2130 * us)],
        async_ops=[],
        modules=[Event("jit_fused(123)", t0 * us, (t0 + 1000) * us) for t0 in starts]
        + [Event("jit_add_frames(9)", 2050 * us, 2130 * us)])
    spans = [Event("bench:force", 0.0, 10 * us), Event("bench:force", 3000 * us, 3300 * us)]
    return Trace({"/device:TPU:0": dev}, spans)


def _readings(**over):
    cfg = mf.load_json(os.path.join(mf.HERE, "configs", "granite4h_q_l10.json"))
    base = dict(trace=_trace(), fused_program="jit_fused", trace_reduce=tr, config=cfg,
                counters={"attention_blocks_visited_full_per_step": 3.0 * 8 * 32 * 3,
                          "attention_blocks_total_full_per_step": 3.0 * 8 * 32 * 4},
                end_to_end={"learn_samples_per_s": 6.0},
                peaks=json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"])
    return types.SimpleNamespace(**dict(base, **over))


def test_a_kernel_takes_the_part_of_its_operands():
    import torso_times

    parts = torso_times.instruction_parts(HLO)
    import blocks_times

    # the scan's products are XLA's and carry their own scope; by its consumers
    # alone the attention kernel would be the mixer's
    assert parts["ssm_chunk.22"] == "ssm_scan" and parts["splash_mha_dkv.26"] == "mixer"
    assert blocks_times.kernel_parts(HLO, parts) == {"splash_mha_dkv.26": "attn_full"}


def test_the_five_parts_add_up_to_the_programs_time(monkeypatch):
    monkeypatch.setattr(st, "program_texts", lambda name: ["HloModule unrelated\n", HLO])
    r = _readings()
    table = ht.table(r)
    assert {k: v for k, v in table.items() if k != "rest"} == pytest.approx(WANT)
    # the gather, the optimizer, the while's own time and the time with no op,
    # and the ingest program's 80 us a call
    assert table["rest"] == pytest.approx(50 + 100 + 40 + 50 + 80)
    fused_us, runs = tr.module_seconds(r.trace, "jit_fused", *tr.span_window(r.trace))
    assert runs == 2 and sum(table.values()) == pytest.approx(fused_us / 2 * 1e6 + 80)
    cell = mf.Cell(mf.load_manifest(), CELL)
    mine = [m["name"] for m in cell.per_layer() if m["name"].startswith("hybrid.")]
    got = {n: cell.reader(n)(r) for n in mine}
    steps = [n for n in got if n.endswith("_step_us")]
    assert len(steps) == 5 and sum(got[n] for n in steps) == pytest.approx(sum(table.values()))
    import ops_count_granite_h_q as ops
    assert got["hybrid.ssm_scan_roofline"] == pytest.approx(
        ops.scan_floor_s(r.config, r.peaks)[0] / (WANT["ssm_scan"] * 1e-6) * 100)
    assert got["hybrid.attn_full_roofline"] == pytest.approx(
        ops.attention_floor_s(r.config, r.peaks, "full")[0] / (WANT["attn_full"] * 1e-6) * 100)
    assert got["hybrid.mfu_pct"] == pytest.approx(ops.flops_per_sample(r.config) * 6.0 / 197e12 * 100)
    assert 25 < got["hybrid.mfu_pct"] < 45


def test_a_program_without_the_scopes_gives_no_metric(monkeypatch):
    monkeypatch.setattr(st, "program_texts", lambda name: [HLO.replace("torso:ssm_", "torso:x_")])
    r = _readings(counters={})
    cell = mf.Cell(mf.load_manifest(), CELL)
    for m in cell.per_layer():
        if m["name"].startswith("hybrid.") and m["name"] != "hybrid.mfu_pct":
            assert cell.reader(m["name"])(r) is None, m["name"]
    # a configuration whose count knows no scan gives no share of the peak here
    other = _readings(config=mf.load_json(os.path.join(mf.HERE, "configs", "laguna_q_ep32.json")))
    assert cell.reader("hybrid.mfu_pct")(other) is None


def test_the_manifests_new_entries():
    m = mf.load_manifest()
    cell = mf.Cell(m, CELL)
    assert cell.chips == 1 and cell.traffic_name == "learner_feed_collected"
    assert cell.config["network"] == "granite_hybrid" and cell.config["reference"] == "granite_h_q"
    entry = [c for c in m["configs"] if c["name"] == "granite4h_q_l10"][0]
    assert entry["reduced"] == cell.config["reduced"] == ["num_hidden_layers", "replay_capacity"]
    assert (m["configs"][-1], m["workloads"][-1]["name"]) == (entry, CELL)     # appended
    mine = [x for x in m["per_layer"] if x["name"].startswith("hybrid.")]
    assert len(mine) == 8 and m["per_layer"][-8:] == mine
    assert all(x["workloads"] == [CELL] and x["layer"] == "learner"
               and x["moves"] == "learn_samples_per_s" for x in mine)
    assert not any(x["name"].endswith("_us_per_step") for x in mine)
    listed = [x["name"] for x in m["per_layer"] if CELL in x.get("workloads", ())]
    # the eight stage lists and no other that was there: tests/benchmark/test_benchmark_laguna_cell.py
    # holds every ``blocks.*`` metric to Laguna's cell alone
    assert len(listed) == 8 + 8 and not any(n.startswith(("blocks.", "torso.", "moe.")) for n in listed)
    assert {n for n in listed if n.endswith("_us_per_step")} == {
        x["name"] for x in m["per_layer"] if x["name"].endswith("_us_per_step") and "workloads" in x}
    reported = {x["name"] for x in cell.per_layer()}
    assert {"ingest.ms_per_call", "fused.us_per_step", "device.idle_pct",
            "device.peak_hbm_gb"} <= reported and "torso.mfu_pct" not in reported
    # the published widths, uncut, and the catalog's numbers under their keys
    c = cell.config
    assert (c["hidden_size"], c["shared_intermediate_size"], c["mamba_n_heads"], c["mamba_d_head"],
            c["mamba_d_state"], c["mamba_d_conv"], c["mamba_expand"], c["mamba_n_groups"],
            c["mamba_chunk_size"]) == (2048, 8192, 64, 64, 128, 4, 2, 1, 256)
    assert (c["num_attention_heads"], c["num_key_value_heads"], c["attention_multiplier"],
            c["embedding_multiplier"], c["residual_multiplier"], c["logits_scaling"]) == (
                32, 8, 0.015625, 12, 0.22, 8)
    assert c["num_hidden_layers"] == 10 == len(c["layers_held"]) and len(c["layer_types"]) == 40
    held = [c["layer_types"][i] for i in c["layers_held"]]
    assert held == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert c["published"] == {"num_hidden_layers": 40} and c["num_local_experts"] == 0


@pytest.mark.parametrize("entry", mf.load_manifest()["configs"], ids=lambda c: c["name"])
def test_every_cut_states_its_reason(entry):
    """A configuration's file may not be edited once it is accepted, so what
    it says of each cut is said before: ``reduced_why`` holds a sentence with
    numbers for every key of ``reduced``, and no placeholder."""
    cfg = mf.load_json(os.path.join(mf.ROOT, entry["file"]))
    assert set(cfg.get("reduced_why", {})) == set(entry["reduced"]) == set(cfg.get("reduced", []))
    for key, why in cfg.get("reduced_why", {}).items():
        assert len(why) >= 80 and "->" in why and any(ch.isdigit() for ch in why), (key, why)
        assert not any(mark in why.upper() for mark in ("TO FILL", "TODO", "TBD", "FIXME", "XXX")), key


def _toy_config():
    return dict(mf.load_json(os.path.join(mf.HERE, "configs", "granite4h_q_l10.json")), **SMALL)


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
cell = mf.Cell(mf.load_manifest(sys.argv[1]), "toy_granite.learner", root=sys.argv[1],
               bench_dir=sys.argv[1] + "/benchmark")
args = types.SimpleNamespace(seed=2**31 + 77, seconds=0.5, trace=0)
print(json.dumps(run.measure(cell, args, jax.devices(), peaks=None)))
"""


def test_toy_granite_cell_runs_and_is_correct(tmp_path):
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(mf.HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    toy = _toy_config()
    with open(os.path.join(root, "benchmark", "configs", "toy_granite.json"), "w") as f:
        json.dump(toy, f)
    with open(os.path.join(root, "benchmark", "limits", "toy_granite.json"), "w") as f:
        json.dump({name: {"limit": limit} for name, limit in TOY_LIMITS.items()}, f)
    with open(os.path.join(root, "benchmark", "traffic", "toy_collected.json"), "w") as f:
        json.dump(_toy_traffic(), f)
    m = mf.load_manifest(root)
    m["configs"].append({"name": "toy_granite", "source": "test",
                         "file": "benchmark/configs/toy_granite.json",
                         "reduced": toy["reduced"], "why": "test"})
    m["workloads"].append({"name": "toy_granite.learner", "config": "toy_granite",
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
    assert "'attention_blocks_visited_full_per_step'" in counters
    assert "held_pairs" not in counters          # no expert layer, no routing counters
    assert "routing by call" not in p.stdout


def test_the_comparison_sees_a_lost_carry():
    """The comparison's two calls at the toy size with the program computing
    in float32, so that its own rounding is out of the way: the program reads
    far under every limit, and the reference whose state is set to zero at
    every chunk boundary, in the program's place, reads over five times the
    program's on every number: the comparison sees a scan that lost its carry.
    Read as ``check_flag_control.py`` reads it on the chip: the flag as one
    more of the driver's controls (the chip's readings and the limits that see
    it are in ``limits/granite4h_q_l10.json``)."""
    import check_flag_control

    cfg = _toy_config()
    cfg["precision"] = dict(cfg["precision"], compute="float32", target_params="float32",
                            second_moment="float32")
    traffic, beta = _toy_traffic(), float(_toy_traffic()["beta"])
    drv = mf.load_module(os.path.join(mf.HERE, "drivers", "learner_feed_collected.py"),
                         "bench_driver_learner_feed_collected")
    inputs, shots = drv.check_shots(cfg, traffic, 2**31 + 9)
    counts, got, reference = drv.base.program_numbers(cfg, beta, inputs, shots)
    assert counts == dict.fromkeys(counts, 0) and shots["routing"] == {}
    assert all(got[name] <= 0.1 * limit for name, limit in TOY_LIMITS.items()), got
    before = dict(drv.base.CONTROLS)
    with check_flag_control.flags_as_controls(drv.base, ["reference_resets_state"]) as base:
        assert list(base.CONTROLS) == ["reference_resets_state"]
        numbers = base.control_numbers(cfg, beta, inputs, shots, reference,
                                       *base.CONTROLS["reference_resets_state"])
    assert drv.base.CONTROLS == before
    assert all(numbers[name] > 5 * got[name] for name in got), (numbers, got)
