"""BENCHMARK.json against the contract's static rules, and every file it
names found by name."""
import json
import os
import re

import pytest

import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = mf.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert len(json.dumps(M)) < 64 * 1024
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
    n_cells = 24  # the check must fit with the full 24 cells
    assert (2 + 14 * n_cells) * (M["run_seconds"] + 60) + n_cells * 180 + 1200 <= 43200


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in M[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in M["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    for m in M["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for e in M["configs"] + M["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"]
    for w in M["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert len({(w["config"], w["traffic"]) for w in M["workloads"]}) == len(M["workloads"])
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 4)
    assert "setup_s" in {m["name"] for m in M["end_to_end"]}


def test_paths_hold_only_the_benchmark():
    for p in M["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and os.path.isdir(os.path.join(mf.ROOT, p))
    for c in M["configs"]:
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        assert os.path.isfile(os.path.join(mf.ROOT, c["file"]))
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = mf.Cell(M, name)
    assert hasattr(cell.driver(), "run")
    assert cell.chips == cell.config["chips"]
    cfg_entry = [c for c in M["configs"] if c["name"] == cell.config_name][0]
    assert cfg_entry["reduced"] == cell.config["reduced"]
    for key in ("source", "reduced", "assumed"):
        assert key in cell.config
    e2e = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.per_layer()
    assert layer
    for m in layer:
        assert callable(cell.reader(m["name"]))
        assert m["moves"] in e2e, (m["name"], "moves a metric this cell does not report")


def test_every_layer_metric_moves_a_reported_metric():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell_name in m.get("workloads", CELLS):
            assert m["moves"] in {x["name"] for x in mf.Cell(M, cell_name).end_to_end()}


def test_b512_pair_shares_one_layout_and_shard():
    a = mf.Cell(M, "apex_b512.learner").config
    b = mf.Cell(M, "apex_b512_dp4.learner").config
    same = ("replay_layout", "frame_ratio", "channels", "hidden", "obs_shape", "batch_size",
            "steps_per_call", "ingest_block", "precision", "loss", "max_grad_norm")
    assert all(a[k] == b[k] for k in same)
    assert a["replay_capacity"] == b["replay_capacity"] // b["data_parallel"]


@pytest.mark.parametrize("config", [c["name"] for c in M["configs"]])
def test_limits_are_read_from_data_and_state_their_readings(config):
    import correctness

    limits = correctness.load_limits(config)
    assert set(limits) == {"fused_priority_rel", "fused_priority_median_rel", "fused_update_rel"}
    with open(os.path.join(mf.HERE, "limits", config + ".json")) as f:
        for name, row in json.load(f).items():
            assert {"limit", "sound_max", "control", "control_min", "readings"} <= set(row)
            assert 3 * row["sound_max"] <= row["control_min"], (config, name)
            assert row["sound_max"] < row["limit"] < row["control_min"], (config, name)


def test_the_benchmarks_tests_are_under_paths():
    here = os.path.relpath(os.path.dirname(os.path.abspath(__file__)), mf.ROOT)
    assert here in M["paths"]


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(mf.ManifestError):
        mf.Cell(M, "no_such.cell")
    with pytest.raises(mf.ManifestError):
        mf.load_json(str(tmp_path / "missing.json"))
