"""The harness is driven by data: a toy configuration (its sizes, its limits) and a cell
dropped into a copy run with no edit to any file that was there.  And run.py measures
nothing without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import manifest as mf

ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=mf.ROOT,
           XLA_FLAGS="--xla_force_host_platform_device_count=1")

DRIVE = r"""
import json, sys, types
sys.path[:0] = [sys.argv[1] + "/benchmark", sys.argv[1]]
import jax
import manifest as mf, run
run.live_peak_bytes = lambda devs: 0     # the CPU backend reports no memory_stats
cell = mf.Cell(mf.load_manifest(sys.argv[1]), "toy.learner", root=sys.argv[1],
               bench_dir=sys.argv[1] + "/benchmark")
args = types.SimpleNamespace(seed=2**31 + 99, seconds=0.5, trace=0)
print(json.dumps(run.measure(cell, args, jax.devices(), peaks=None)))
"""


def _hashes(root):
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out


def test_toy_cell_added_as_data_runs(tmp_path):
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(mf.HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(os.path.join(root, "benchmark"))

    toy = mf.load_json(os.path.join(mf.HERE, "configs", "apex_b512.json"))
    toy.update(obs_shape=[36, 36, 4], hidden=32, channels=[8, 8, 8], batch_size=8,
               replay_capacity=1024, steps_per_call=4, ingest_block=64, target_sync_freq=8)
    with open(os.path.join(root, "benchmark", "configs", "toy.json"), "w") as f:
        json.dump(toy, f)
    with open(os.path.join(root, "benchmark", "limits", "toy.json"), "w") as f:
        json.dump({"fused_priority_rel": {"limit": 0.1}, "fused_priority_median_rel": {"limit": 0.05},
                   "fused_update_rel": {"limit": 0.5}}, f)
    m = mf.load_manifest(root)
    m["configs"].append({"name": "toy", "source": "test", "file": "benchmark/configs/toy.json",
                         "reduced": toy["reduced"], "why": "test"})
    m["workloads"].append({"name": "toy.learner", "config": "toy", "traffic": "learner_feed",
                           "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    p = subprocess.run([sys.executable, "-c", DRIVE, root], env=ENV, cwd=root,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    assert set(result["metrics"]) == {"learn_samples_per_s", "setup_s"}
    assert result["metrics"]["learn_samples_per_s"]["value"] > 0
    for what in ("step counter", "ring_rows_differing", "masses_unexplained",
                 "rows_outside_stratum", "fused_priority_rel", "fused_priority_median_rel",
                 "fused_update_rel"):
        assert f"compare {what} = " in p.stdout, what
    after = _hashes(os.path.join(root, "benchmark"))
    after.pop(os.path.join("configs", "toy.json"))
    after.pop(os.path.join("limits", "toy.json"))
    assert after == before  # nothing that was there changed


def test_run_py_refuses_the_cpu():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "ref_b32.learner",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=ENV, cwd=mf.ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_py_refuses_a_directory_without_the_program(tmp_path):
    root = str(tmp_path / "alone")
    os.makedirs(root)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(mf.HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "ref_b32.learner",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "program is not beside" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
