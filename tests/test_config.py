"""Config system tests: reference-JSON parity load, validation, overrides."""

import json

import pytest

from ape_x_dqn_tpu.config import (
    ApexConfig,
    apply_overrides,
    from_reference_json,
    load_config,
)

REFERENCE_STYLE = {
    "env_conf": {"state_shape": [1, 84, 84], "action_dim": 4,
                 "name": "RiverraidNoFrameskip-v4"},
    "Actor": {"num_actors": 5, "T": 50000, "num_steps": 3, "epsilon": 0.4,
              "alpha": 7, "gamma": 0.99, "n_step_transition_batch_size": 5,
              "Q_network_sync_freq": 500},
    "Learner": {"remove_old_xp_freq": 100, "q_target_sync_freq": 2500,
                "min_replay_mem_size": 20000, "replay_sample_size": 32,
                "load_saved_state": False},
    "Replay_Memory": {"soft_capacity": 100000, "priority_exponent": 0.6,
                      "importance_sampling_exponent": 0.4},
}


def test_reference_json_roundtrip():
    cfg = from_reference_json(REFERENCE_STYLE)
    assert cfg.actor.num_actors == 5
    assert cfg.actor.num_steps == 3
    assert cfg.actor.sync_every == 500
    assert cfg.learner.q_target_sync_freq == 2500
    assert cfg.learner.min_replay_mem_size == 20000
    assert cfg.replay.capacity == 100000
    assert cfg.replay.priority_exponent == 0.6
    assert cfg.replay.is_exponent == 0.4  # dead in the reference, live here
    assert cfg.env.name == "RiverraidNoFrameskip-v4"


def test_unknown_reference_key_rejected():
    bad = {"Actor": {"num_actors": 5, "warp_speed": 9}}
    with pytest.raises(ValueError, match="unknown config key"):
        from_reference_json(bad)


def test_validation_catches_bad_values():
    cfg = ApexConfig()
    cfg.actor.epsilon = 1.5
    with pytest.raises(ValueError, match="epsilon"):
        cfg.validate()
    cfg = ApexConfig()
    cfg.replay.capacity = 8
    cfg.learner.replay_sample_size = 32
    with pytest.raises(ValueError, match="capacity"):
        cfg.validate()
    cfg = ApexConfig()
    cfg.network = "transformer"
    with pytest.raises(ValueError, match="network"):
        cfg.validate()


def test_overrides():
    cfg = apply_overrides(ApexConfig(), ["actor.num_actors=64", "network=mlp",
                                         "learner.learning_rate=0.001"])
    assert cfg.actor.num_actors == 64
    assert cfg.network == "mlp"
    assert cfg.learner.learning_rate == 0.001


# Options a config or a command line of an earlier tree may still name:
# gone with the overlapped dispatch loop, they fail like any unknown key.
REMOVED_OPTIONS = [
    ("learner", "pipeline_depth", 2),
    ("learner", "sync_every", 64),
    ("chaos", "stuck_stager_interval_s", 1.0),
    ("chaos", "stuck_stager_hold_s", 1.0),
]
UNKNOWN_OPTIONS = [("actor", "bogus", 1)] + REMOVED_OPTIONS
unknown_options = pytest.mark.parametrize(
    "section,field,value", UNKNOWN_OPTIONS,
    ids=[f"{s}.{f}" for s, f, _ in UNKNOWN_OPTIONS])


@unknown_options
def test_override_unknown_path_rejected(section, field, value):
    with pytest.raises(ValueError, match="unknown config"):
        apply_overrides(ApexConfig(), [f"{section}.{field}={value}"])


def test_load_config_file_formats(tmp_path):
    ref = tmp_path / "params.json"
    ref.write_text(json.dumps(REFERENCE_STYLE))
    cfg = load_config(str(ref))
    assert cfg.actor.num_actors == 5

    native = tmp_path / "native.json"
    native.write_text(json.dumps(
        {"actor": {"num_actors": 3}, "network": "mlp", "seed": 42}
    ))
    cfg = load_config(str(native), overrides=["actor.gamma=0.95"])
    assert cfg.actor.num_actors == 3 and cfg.seed == 42
    assert cfg.actor.gamma == 0.95


@unknown_options
def test_native_unknown_key_rejected(tmp_path, section, field, value):
    native = tmp_path / "native.json"
    native.write_text(json.dumps({section: {field: value}}))
    with pytest.raises(ValueError, match="unknown config keys"):
        load_config(str(native))


def test_every_torso_network_has_a_row_of_the_contract_and_a_file_that_runs_it():
    """``tests/torso_contract.py`` states what a torso's network is held to; a
    name in ``TORSO_NETWORKS`` with no row there, or no test file whose
    ``TestContract`` is ``contract.of("<name>")``, is held to nothing."""
    import pathlib
    import re

    from ape_x_dqn_tpu.config import TORSO_NETWORKS
    from tests import torso_contract

    assert set(torso_contract.ROWS) == set(TORSO_NETWORKS)
    tests = pathlib.Path(__file__).parent
    run = {m for f in tests.glob("test_*.py") for m in re.findall(
        r'^class TestContract\(contract\.of\("(\w+)"\)\):', f.read_text(), re.M)}
    assert run == set(TORSO_NETWORKS), set(TORSO_NETWORKS) ^ run
