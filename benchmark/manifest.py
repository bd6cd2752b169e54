"""BENCHMARK.json and the files it names: everything is found by name.

A cell's configuration is the ``file`` of its entry under ``configs``; its
traffic mix is ``traffic/<traffic>.json`` beside this file, which names the
driver in ``drivers/<driver>.py``; a per-layer metric ``<name>`` is read by
``layer_metrics/<name>.py``.  Adding a cell, a mix or a metric adds files
and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ManifestError(ValueError):
    pass


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _one(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise ManifestError(f"{what} {name!r}: {len(found)} entries in BENCHMARK.json")
    return found[0]


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise ManifestError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""

    def __init__(self, manifest: dict, workload: str, root: str = ROOT,
                 bench_dir: str = HERE):
        self.manifest = manifest
        self.workload = _one(manifest["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.workload["chips"])
        cfg_entry = _one(manifest["configs"], self.workload["config"], "config")
        self.config_name = cfg_entry["name"]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic_name = self.workload["traffic"]
        self.traffic = load_json(
            os.path.join(bench_dir, "traffic", self.traffic_name + ".json")
        )
        self.bench_dir = bench_dir

    def driver(self):
        name = self.traffic["driver"]
        return load_module(
            os.path.join(self.bench_dir, "drivers", name + ".py"),
            "bench_driver_" + name,
        )

    def _reports(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list:
        """The per-layer metrics this cell reports: those that list it, and
        those with no list whose ``moves`` metric this cell reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m else m["moves"] in e2e)]

    def reader(self, metric_name: str):
        mod = load_module(
            os.path.join(self.bench_dir, "layer_metrics", metric_name + ".py"),
            "bench_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        )
        return mod.read
