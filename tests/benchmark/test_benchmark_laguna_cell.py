"""The cell ``laguna_q_ep32.learner``: its ``blocks.*`` readers on a hand-made
program text and trace, and the cell at a toy size on the CPU, where a copy of
its configuration with small widths runs through ``run.measure`` under the
driver ``learner_feed_collected`` and comes out correct, and the reference
with the window ignored does not."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import blocks_times as bt
import manifest as mf
import stage_times as st
import trace_reduce as tr
from trace_reduce import DeviceTrace, Event, Trace

ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=mf.ROOT,
           XLA_FLAGS="--xla_force_host_platform_device_count=1")

SMALL = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_key_value_heads=2, head_dim=16, sliding_window=8,
    num_attention_heads_per_layer=[4, 6, 6, 6] * 12, num_experts=4, router_outputs=16,
    experts_held=[0, 4], num_experts_per_tok=3, obs_shape=[44, 44, 10], hidden=32,
    channels=[8, 8, 8], batch_size=8, replay_capacity=512, steps_per_call=1, ingest_block=16,
    target_sync_freq=8, num_actions=6,
)

# At hidden 64, 40 tokens and batch 8 on the CPU (read while writing this,
# seeds 2**31 + 9, 2**31 + 77 and 12345): program 0.029-0.090 / 0.019-0.052 /
# 0.40-0.48; the reference with the window ignored, in the program's place,
# 0.143-0.202 / 0.094-0.211 / 0.82-1.01.  A router of 16 outputs at 64 wide
# flips experts on bfloat16 rounding and moves the whole update, so these
# limits are this test's alone, and its seeds are fixed.
TOY_LIMITS = {"fused_priority_rel": 0.13, "fused_priority_median_rel": 0.085,
              "fused_update_rel": 0.68}

HLO = """HloModule jit_fused, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(fused)/stage:sample/neg"}
}

%body.2 (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%t), index=1
  %fusion.20 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/LagunaMoeQ/layer_0/torso:mixer/full_attention/btd,dnk->bntk/dot_general"}
  %pad.21 = f32[4]{0} pad(%fusion.20), metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/LagunaMoeQ/layer_0/torso:mixer/full_attention/torso:attn_full/pad"}
  %splash_mha_fwd.22 = f32[4]{0} custom-call(%pad.21), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4]{0}}
  %fusion.23 = f32[4]{0} fusion(%splash_mha_fwd.22), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/LagunaMoeQ/layer_0/torso:mixer/full_attention/mul"}
  %fusion.24 = f32[4]{0} fusion(%fusion.23), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/LagunaMoeQ/layer_0/torso:dense_ffn/dense/dot_general"}
  %pad.25 = f32[4]{0} pad(%fusion.24), metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/LagunaMoeQ/layers_1_3/torso:mixer/sliding_attention/torso:attn_window/pad"}
  %splash_mha_dkv.26 = f32[4]{0} custom-call(%pad.25, %pad.25), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4]{0}}
  %fusion.27 = f32[4]{0} fusion(%splash_mha_dkv.26), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/transpose(jvp(stage:forward))/LagunaMoeQ/layers_1_3/torso:router/moe/top_k"}
  %ragged-dot.28 = f32[4]{0} custom-call(%fusion.27), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4]{0}}
  %fusion.29 = f32[4]{0} fusion(%ragged-dot.28), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/LagunaMoeQ/layers_1_3/torso:experts/moe/mul"}
  %fusion.30 = f32[4]{0} fusion(%fusion.29), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/jvp(stage:forward)/LagunaMoeQ/layers_1_3/torso:shared_expert/shared_expert/dot_general"}
  %fusion.31 = f32[4]{0} fusion(%fusion.30), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fused)/while/body/stage:optimizer/sub"}
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
OPS = [("fusion.9", 0, 50), ("while.13", 50, 900), ("fusion.20", 60, 200), ("pad.21", 260, 10),
       ("splash_mha_fwd.22", 270, 90), ("fusion.23", 360, 40), ("fusion.24", 400, 100),
       ("pad.25", 500, 10), ("splash_mha_dkv.26", 510, 120), ("fusion.27", 630, 80),
       ("ragged-dot.28", 710, 30), ("fusion.29", 740, 20), ("fusion.30", 760, 60),
       ("fusion.31", 820, 100)]
WANT = {"attn_full": 100, "attn_window": 130, "mixer": 240, "dense_ffn": 100, "router": 80,
        "experts": 50, "shared_expert": 60}


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
    cfg = mf.load_json(os.path.join(mf.HERE, "configs", "laguna_q_ep32.json"))
    base = dict(trace=_trace(), fused_program="jit_fused", trace_reduce=tr, config=cfg,
                counters={"held_pairs_per_step": 47040.0, "load_max_per_step": 9000.0,
                          "load_mean_per_step": 6000.0,
                          # a step's three forwards at batch 8, as the fused calls count them
                          "attention_blocks_visited_full_per_step": 3.0 * 8 * 2 * 48 * 3,
                          "attention_blocks_total_full_per_step": 3.0 * 8 * 2 * 48 * 4,
                          "attention_blocks_visited_window_per_step": 3.0 * 8 * 3 * 72 * 7,
                          "attention_blocks_total_window_per_step": 3.0 * 8 * 3 * 72 * 16},
                end_to_end={"learn_samples_per_s": 9.0},
                peaks=json.load(open(os.path.join(mf.HERE, "peaks.json")))["TPU v5 lite"])
    return types.SimpleNamespace(**dict(base, **over))


def test_a_kernel_takes_the_part_of_the_padding_that_feeds_it():
    import torso_times

    parts = torso_times.instruction_parts(HLO)
    # by its consumers alone the forward kernel is the mixer's and the backward one the router's
    assert parts["splash_mha_fwd.22"] == "mixer" and parts["splash_mha_dkv.26"] == "router"
    assert bt.kernel_parts(HLO, parts) == {"splash_mha_fwd.22": "attn_full",
                                           "splash_mha_dkv.26": "attn_window"}
    assert parts["ragged-dot.28"] == "experts"     # the grouped kernels keep their consumers' part


def test_the_eight_parts_add_up_to_the_programs_time(monkeypatch):
    monkeypatch.setattr(st, "program_texts", lambda name: ["HloModule unrelated\n", HLO])
    r = _readings()
    table = bt.table(r)
    assert {k: v for k, v in table.items() if k != "rest"} == pytest.approx(WANT)
    # the gather, the optimizer, the while's own time and the time with no op,
    # and the ingest program's 80 us a call
    assert table["rest"] == pytest.approx(50 + 100 + 40 + 50 + 80)
    fused_us, runs = tr.module_seconds(r.trace, "jit_fused", *tr.span_window(r.trace))
    assert runs == 2 and sum(table.values()) == pytest.approx(fused_us / 2 * 1e6 + 80)
    m = mf.load_manifest()
    mine = [x for x in m["per_layer"] if x["name"].startswith("blocks.")]
    assert len(mine) == 11 and all(x["workloads"] == ["laguna_q_ep32.learner"] for x in mine)
    # what reads the same on both expert cells has one name and lists both
    shared = ["torso.experts_roofline", "torso.mfu_pct", "moe.held_pairs_per_step",
              "moe.load_max_over_mean"]
    both = ["lfm2moe_q_ep8.learner", "laguna_q_ep32.learner"]
    assert [x["name"] for x in m["per_layer"] if x.get("workloads") == both] == shared
    cell = mf.Cell(m, "laguna_q_ep32.learner")
    got = {n: cell.reader(n)(r) for n in [x["name"] for x in mine] + shared}
    steps = [n for n in got if n.endswith("_step_us")]
    assert len(steps) == 8 and sum(got[n] for n in steps) == pytest.approx(sum(table.values()))
    import ops_count_laguna_q as ops
    for kind in ("full", "window"):
        floor = ops.attention_floor_s(r.config, r.peaks, kind)[0]
        assert got[f"blocks.attn_{kind}_roofline"] == pytest.approx(
            floor / (WANT["attn_" + kind] * 1e-6) * 100)
    # torso_times' table knows no kernel rule, and its experts are the same 50 us
    assert got["torso.experts_roofline"] == pytest.approx(
        ops.expert_floor_s(r.config, r.peaks, 47040.0)[0] / 50e-6 * 100)
    assert got["torso.mfu_pct"] == pytest.approx(
        ops.flops_per_sample(r.config, 47040.0) * 9.0 / 197e12 * 100)
    assert 25 < got["torso.mfu_pct"] < 45
    assert got["moe.held_pairs_per_step"] == 47040.0
    assert got["moe.load_max_over_mean"] == pytest.approx(1.5)
    # the fused calls' own count: 2 full layers of 48 heads visit 3 of 4 blocks,
    # 3 sliding layers of 72 heads 7 of 16
    assert got["blocks.attn_blocks_visited_pct"] == pytest.approx(
        (2 * 48 * 3 + 3 * 72 * 7) / (2 * 48 * 4 + 3 * 72 * 16) * 100)


def test_the_drivers_attention_counters_are_the_fused_calls():
    """What the reader of ``blocks.attn_blocks_visited_pct`` is given: the mean
    over the steps of the calls after the warm-up of what each call returned."""
    drv = mf.load_module(os.path.join(mf.HERE, "drivers", "learner_feed_collected.py"),
                         "bench_driver_learner_feed_collected")
    import numpy as np
    calls = [{"blocks_visited_window": np.full(2, 7.0), "blocks_total_window": np.full(2, 16.0)},
             {"blocks_visited_window": np.full(2, 9.0), "blocks_total_window": np.full(2, 16.0)}]
    assert drv.attention_counters(calls, 4) == {
        "attention_blocks_visited_window_per_step": 8.0,
        "attention_blocks_total_window_per_step": 16.0}
    assert drv.attention_counters([], 0) == {}
    cell = mf.Cell(mf.load_manifest(), "laguna_q_ep32.learner")
    read = cell.reader("blocks.attn_blocks_visited_pct")
    assert read(types.SimpleNamespace(counters=drv.attention_counters(calls, 4))) == 50.0
    assert read(types.SimpleNamespace(counters={"held_pairs_per_step": 1.0})) is None


def test_a_program_without_the_scopes_or_the_network_gives_no_metric(monkeypatch):
    monkeypatch.setattr(st, "program_texts", lambda name: [HLO.replace("torso:attn_", "torso:x_")])
    r = _readings(counters={}, config=dict(_readings().config, network="no_such_network"))
    cell = mf.Cell(mf.load_manifest(), "laguna_q_ep32.learner")
    for m in cell.per_layer():
        if m["name"].startswith(("blocks.", "torso.", "moe.")):
            assert cell.reader(m["name"])(r) is None, m["name"]


def _toy_config():
    return dict(mf.load_json(os.path.join(mf.HERE, "configs", "laguna_q_ep32.json")), **SMALL)


DRIVE = r"""
import json, sys, types
sys.path[:0] = [sys.argv[1] + "/benchmark", sys.argv[1]]
import jax
import manifest as mf, run
run.live_peak_bytes = lambda devs: 0     # the CPU backend reports no memory_stats
cell = mf.Cell(mf.load_manifest(sys.argv[1]), "toy_laguna.learner", root=sys.argv[1],
               bench_dir=sys.argv[1] + "/benchmark")
args = types.SimpleNamespace(seed=2**31 + 77, seconds=0.5, trace=0)
print(json.dumps(run.measure(cell, args, jax.devices(), peaks=None)))
"""


def test_toy_laguna_cell_runs_and_is_correct(tmp_path):
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(mf.HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    toy = _toy_config()
    with open(os.path.join(root, "benchmark", "configs", "toy_laguna.json"), "w") as f:
        json.dump(toy, f)
    with open(os.path.join(root, "benchmark", "limits", "toy_laguna.json"), "w") as f:
        json.dump({name: {"limit": limit} for name, limit in TOY_LIMITS.items()}, f)
    traffic = mf.load_json(os.path.join(mf.HERE, "traffic", "learner_feed_collected.json"))
    traffic["check"] = dict(traffic["check"], ring_rows_per_chip=256, ingest_rows_per_chip=32)
    with open(os.path.join(root, "benchmark", "traffic", "toy_collected.json"), "w") as f:
        json.dump(traffic, f)
    m = mf.load_manifest(root)
    m["configs"].append({"name": "toy_laguna", "source": "test",
                         "file": "benchmark/configs/toy_laguna.json",
                         "reduced": toy["reduced"], "why": "test"})
    m["workloads"].append({"name": "toy_laguna.learner", "config": "toy_laguna",
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
    assert "'held_pairs_per_step'" in counters and "'load_max_per_step'" in counters
    assert "'attention_blocks_visited_window_per_step'" in counters
    assert "attention a step, as the fused calls after the warm-up count it" in p.stdout
    assert p.stdout.count("priorities over the reference's, less one:") == 2


def test_the_traffic_is_the_by_name_drivers_with_a_settled_second_moment():
    import correctness

    mine = mf.load_json(os.path.join(mf.HERE, "traffic", "learner_feed_collected.json"))
    theirs = mf.load_json(os.path.join(mf.HERE, "traffic", "learner_feed_by_name.json"))
    assert mine["driver"] == "learner_feed_collected"
    assert mine.pop("settled_second_moment") == correctness.NU0
    assert {k: v for k, v in mine.items() if k not in ("driver", "what")} == {
        k: v for k, v in theirs.items() if k not in ("driver", "what")}


def test_the_timed_state_is_the_seeded_one_with_a_settled_second_moment():
    import jax
    import numpy as np
    import program
    from spans import Spans

    cfg = _toy_config()
    traffic = mf.load_json(os.path.join(mf.HERE, "traffic", "learner_feed_collected.json"))
    drv = mf.load_module(os.path.join(mf.HERE, "drivers", "learner_feed_collected.py"),
                         "bench_driver_learner_feed_collected")
    key = program.seed_key(2**31 + 5)
    mine, theirs = drv.Feed(cfg, traffic, key, Spans()), drv.base.Feed(cfg, traffic, key, Spans())
    assert drv.made == [mine] and not drv.made.clear()
    for a, b in zip(_leaves(mine.state.params), _leaves(theirs.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moments = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(mine.state.opt_state),
                            _leaves(theirs.state.opt_state)):
        if "nu" in jax.tree_util.keystr(path):
            moments += 1
            want = np.asarray(traffic["settled_second_moment"], a.dtype)
            assert np.all(np.asarray(a) == want) and not np.any(np.asarray(b))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert moments == len(_leaves(mine.state.params))


def test_the_comparison_sees_a_wrong_mask(capsys):
    """The comparison's two calls at the toy size under the collecting driver
    (the inputs on the host before the state is made, the by-name driver's
    functions put back after): the program passes; the reference with the
    window ignored on the sliding layers, in the program's place, reads over
    a limit."""
    cfg = _toy_config()
    traffic = mf.load_json(os.path.join(mf.HERE, "traffic", "learner_feed_collected.json"))
    traffic["check"] = dict(traffic["check"], ring_rows_per_chip=256, ingest_rows_per_chip=32)
    drv = mf.load_module(os.path.join(mf.HERE, "drivers", "learner_feed_collected.py"),
                         "bench_driver_learner_feed_collected")
    kept = (drv.base.check_shots, drv.base.state_from_inputs)
    inputs, shots = drv.check_shots(cfg, traffic, 2**31 + 9)
    assert (drv.base.check_shots, drv.base.state_from_inputs) == kept
    import numpy as np
    assert all(isinstance(x, np.ndarray) for x in _leaves(inputs["target"]))
    counts, got, reference = drv.base.program_numbers(cfg, float(traffic["beta"]), inputs, shots)
    assert counts == dict.fromkeys(counts, 0) and shots["routing"]["held_pairs"] > 0
    for name, limit in TOY_LIMITS.items():
        assert got[name] <= limit, (name, got)
    # the readings' replay prints each row's bootstrap and replays the same
    kept = drv.base._reference_fn
    drv.base._reference_fn = drv.with_bootstrap(kept)
    try:
        again = drv.base.reference_run(cfg, float(traffic["beta"]), inputs, shots)
    finally:
        drv.base._reference_fn = kept
    np.testing.assert_array_equal(again["priorities"], reference["priorities"])
    said = [x for x in capsys.readouterr().out.splitlines() if "bootstrap by row" in x]
    assert len(said) == 2 and all(len(x.split(": ")[1].split(", ")) == 8 for x in said), said
    blind = drv.base.reference_run(dict(cfg, reference_ignores_window=True),
                                   float(traffic["beta"]), inputs, shots)
    numbers = drv.base.compare(inputs["weights"], blind["weights"], blind["priorities"], reference)
    assert any(numbers[name] > limit for name, limit in TOY_LIMITS.items()), numbers


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)
