"""CLI trainer tests: both modes end-to-end via main(argv)."""

import json

import pytest

from ape_x_dqn_tpu.train import main

BASE_ARGS = [
    "--set", "env.name=chain:6",
    "--set", "network=mlp",
    "--set", "actor.num_actors=2",
    "--set", "actor.flush_every=8",
    "--set", "learner.min_replay_mem_size=128",
    "--set", "replay.capacity=2000",
    "--set", "learner.optimizer=adam",
    "--log-every", "20",
]


def test_sync_mode(capsys, tmp_path):
    rc = main(BASE_ARGS + ["--mode", "sync", "--steps", "40",
                           "--metrics-file", str(tmp_path / "m.jsonl")])
    assert rc == 0
    out = capsys.readouterr().out
    records = [json.loads(l) for l in out.splitlines() if l.strip()]
    assert records and records[-1].get("final")
    assert records[-1]["step"] == 40
    assert (tmp_path / "m.jsonl").read_text().strip()


def test_async_mode(capsys):
    rc = main(BASE_ARGS + ["--mode", "async", "--steps", "60"])
    assert rc == 0
    out = capsys.readouterr().out
    records = [json.loads(l) for l in out.splitlines() if l.strip()]
    assert records[-1]["step"] == 60
    assert records[-1]["replay_size"] >= 128


def test_reference_params_file(tmp_path, capsys):
    """The actual reference parameters.json vocabulary drives the CLI."""
    ref = {
        "env_conf": {"state_shape": [6], "action_dim": 2, "name": "chain:6"},
        "Actor": {"num_actors": 2, "T": 1000, "num_steps": 3, "epsilon": 0.4,
                  "alpha": 7, "gamma": 0.9, "n_step_transition_batch_size": 8,
                  "Q_network_sync_freq": 50},
        "Learner": {"remove_old_xp_freq": 100, "q_target_sync_freq": 100,
                    "min_replay_mem_size": 128, "replay_sample_size": 16,
                    "load_saved_state": False},
        "Replay_Memory": {"soft_capacity": 2000, "priority_exponent": 0.6,
                          "importance_sampling_exponent": 0.4},
    }
    f = tmp_path / "params.json"
    f.write_text(json.dumps(ref))
    rc = main(["--params-file", str(f), "--set", "network=mlp",
               "--mode", "sync", "--steps", "10", "--log-every", "5"])
    assert rc == 0


def test_bad_override_exits_with_error():
    with pytest.raises(ValueError):
        main(BASE_ARGS + ["--set", "bogus.key=1", "--steps", "1"])


def test_canonical_configs_load_and_validate():
    """The committed canonical configs (the five BASELINE.md training
    profiles + the serving profile) parse, validate, and carry the runtime
    modes they claim (device replay, data parallel, process actors,
    frame compression, serving buckets)."""
    import glob
    import os

    from ape_x_dqn_tpu.config import load_config

    root = os.path.join(os.path.dirname(__file__), "..", "configs")
    paths = sorted(glob.glob(os.path.join(root, "*.json")))
    assert len(paths) == 14, paths
    cfgs = {os.path.basename(p): load_config(p) for p in paths}
    assert cfgs["config1_pong_1actor.json"].actor.num_actors == 1
    c6 = cfgs["config6_lfm2moe_q_ep8.json"]
    assert c6.network == "lfm2_moe" and c6.torso["hidden_size"] == 2048
    assert c6.learner.device_replay and c6.replay.dedup and c6.learner.steps_per_call == 2
    c7 = cfgs["config7_laguna_q_ep32.json"]
    assert c7.network == "laguna_moe" and c7.torso["hidden_size"] == 3072
    assert c7.env.frame_stack == 32 and c7.learner.replay_sample_size == 8
    assert c7.learner.device_replay and c7.replay.dedup and c7.learner.steps_per_call == 1
    c8 = cfgs["config8_granite4h_q_l10.json"]
    assert c8.network == "granite_hybrid" and c8.torso["mamba_d_state"] == 128
    assert c8.env.frame_stack == 32 and c8.learner.replay_sample_size == 8
    c9 = cfgs["config9_solar2_q_ep40.json"]
    assert c9.network == "solar_open2" and c9.torso["heads_held"] == [0, 16]
    assert c9.env.frame_stack == 32 and c9.learner.replay_sample_size == 8
    c10 = cfgs["config10_ling3_q_l7.json"]
    assert c10.network == "ling_hybrid" and c10.torso["kv_lora_rank"] == 512
    assert c10.torso["heads_held"] == [0, 8] and c10.torso["n_group"] == 8
    assert c10.env.frame_stack == 32 and c10.learner.replay_sample_size == 8
    c11 = cfgs["config11_olmoh_q_l4.json"]
    assert c11.network == "olmo_hybrid" and c11.torso["linear_value_head_dim"] == 192
    assert c11.torso["layers_held"] == [0, 1, 2, 3] and c11.torso["num_attention_heads"] == 30
    assert c11.env.frame_stack == 32 and c11.learner.replay_sample_size == 4
    c12 = cfgs["config12_kanana2_q_ep8.json"]
    assert c12.network == "kanana_moe" and c12.torso["kv_lora_rank"] == 512
    assert c12.torso["experts_held"] == [0, 16] and "heads_held" not in c12.torso
    assert c12.env.frame_stack == 32 and c12.learner.replay_sample_size == 8
    c13 = cfgs["config13_nemotron3s_q_ep32.json"]
    assert c13.network == "nemotron_h" and c13.torso["moe_latent_size"] == 1024
    assert c13.torso["layers_held"] == list(range(27, 38)) and c13.torso["experts_held"] == [0, 16]
    assert (c13.torso["heads_held"], c13.torso["mamba_heads_held"],
            c13.torso["shared_expert_held"]) == ([0, 8], [0, 32], [0, 1344])
    assert c13.env.frame_stack == 32 and c13.learner.replay_sample_size == 8
    assert cfgs["config2_breakout_8actors.json"].actor.num_actors == 8
    c3 = cfgs["config3_seaquest_256actors_2m.json"]
    assert c3.replay.capacity == 2_000_000
    # Paper scale runs the frame-dedup sharded HBM ring (round-4 verdict
    # item 1a): frames stored ONCE, so the 2M ring is capacity ×
    # frame_ratio × 7056 B ≈ 17.6 GB global ≈ 4.4 GB/chip at dp=4 — the
    # double-store's 28 GB could not fit and round 4 fell back to a host
    # replay that sampled below the learner rate.
    assert c3.learner.device_replay and c3.replay.dedup
    assert c3.learner.data_parallel == 4
    per_chip = (
        c3.replay.capacity * c3.replay.frame_ratio * 84 * 84
        / c3.learner.data_parallel
    )
    assert per_chip < 6e9, "config3 ring shard must fit a 16 GB chip easily"
    assert c3.actor.mode == "process"
    assert c3.actor.num_actors // c3.actor.num_workers >= c3.learner.data_parallel
    c4 = cfgs["config4_dp_v4_8_512actors.json"]
    assert c4.learner.data_parallel == 4 and c4.actor.num_actors == 512
    # The north-star mode (BASELINE config 4): fused HBM replay sharded
    # over the DP mesh — 2M slots / 4 devices ≈ 7 GB/device of rings,
    # sized for a v4-8's 32 GB/chip HBM (not single-chip v5e).
    assert c4.learner.device_replay and c4.learner.sample_ahead
    c5 = cfgs["config5_sweep_atari57_base.json"]
    assert c5.learner.device_replay
    c6 = cfgs["config6_serving_cpu.json"]
    assert c6.network == "conv"
    assert c6.serving.max_batch == 32
    assert c6.serving.queue_capacity >= c6.serving.max_batch


def test_sweep_runner_shared_schedule(tmp_path):
    """tools/sweep.py (BASELINE config 5's runner): one run per game under
    one shared schedule, summary JSONL written, bad games don't kill it."""
    import json
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent.parent / "tools"))
    try:
        import sweep
    finally:
        sys.path.pop(0)

    out = tmp_path / "sweep.jsonl"
    results = sweep.run_sweep(
        ["chain:5", "catch", "definitely-not-an-env"],
        steps=20,
        mode="sync",
        out_path=str(out),
        overrides=[
            "network=mlp", "actor.num_actors=2", "actor.T=100000",
            "learner.min_replay_mem_size=64", "replay.capacity=1024",
        ],
    )
    assert [r["status"] for r in results] == ["ok", "ok", "error"]
    assert results[0]["game"] == "chain:5"
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(lines) == 3
    # Shared schedule, distinct seeds per game.
    assert lines[0]["seed"] != lines[1]["seed"]


def test_sweep_atari57_list():
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent.parent / "tools"))
    try:
        import sweep
    finally:
        sys.path.pop(0)
    games = sweep.game_list("atari57")
    assert len(games) == 57
    assert "PongNoFrameskip-v4" in games and "ZaxxonNoFrameskip-v4" in games
