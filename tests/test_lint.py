"""apexlint — the static-analysis suite that enforces the fleet's invariants.

Three layers of coverage:

  * **fixture tests** — each checker pointed at a tiny known-bad tree
    under tests/fixtures/lint/, asserting it fires with the right
    checker id and file:line (and does NOT fire on the blessed idioms);
  * **the repo itself** — the committed tree must lint clean against
    the committed baseline (the pytest twin of verify gate 14), and the
    import-light contract is re-proven DYNAMICALLY by importing each
    contracted module in a subprocess and asserting jax never loads;
  * **doc-schema pins** — the cheap runtime dict-vs-docs/METRICS.md
    comparisons absorbed from test_obs.py (the analyzer's
    ``doc_section_keys`` is now the one shared parser; the pins that
    need a full training run stay with their fixtures in test_obs.py /
    test_central_inference.py / test_replay_svc.py).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from ape_x_dqn_tpu import analysis
from ape_x_dqn_tpu.analysis import (
    config_coverage,
    import_light,
    metrics_doc,
    shm_discipline,
    typed_errors,
    wire_registry,
)
from ape_x_dqn_tpu.analysis.core import IMPORT_LIGHT_CONTRACT, Repo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "lint")


def _by_key(findings):
    return {f.key: f for f in findings}


# ---------------------------------------------------------------------------
# Checker fixture tests: known-bad trees, exact ids and lines.
# ---------------------------------------------------------------------------


class TestImportLightChecker:
    def test_transitive_smuggle_found_with_chain(self):
        repo = Repo(os.path.join(FIXTURES, "import_light"),
                    rel_dirs=("fixpkg",))
        found = import_light.check(repo, roots=("fixpkg.entry",))
        assert len(found) == 1
        f = found[0]
        assert f.checker == "import-light"
        assert f.path == "fixpkg/middle.py" and f.line == 3
        assert f.key == "fixpkg.entry->jax"
        assert "fixpkg.entry -> fixpkg.middle" in f.message

    def test_function_scope_import_is_legal(self):
        repo = Repo(os.path.join(FIXTURES, "import_light"),
                    rel_dirs=("fixpkg",))
        assert import_light.check(repo, roots=("fixpkg.lazy_ok",)) == []

    def test_missing_contract_root_is_a_finding(self):
        repo = Repo(os.path.join(FIXTURES, "import_light"),
                    rel_dirs=("fixpkg",))
        found = import_light.check(repo, roots=("fixpkg.nonexistent",))
        assert [f.key for f in found] == ["missing-root:fixpkg.nonexistent"]


class TestWireRegistryChecker:
    @pytest.fixture()
    def found(self):
        repo = Repo(os.path.join(FIXTURES, "wire"), rel_dirs=("wirepkg",))
        return _by_key(wire_registry.check(
            repo, net_path="wirepkg/net.py", allowed_dupes={},
            wire_plane=()))

    def test_duplicate_kind_value(self, found):
        f = found["dup-kind-value:F_B"]
        assert f.path == "wirepkg/net.py" and f.line == 4

    def test_dead_kind(self, found):
        f = found["dead-kind:F_C"]
        assert f.path == "wirepkg/net.py" and f.line == 5
        # F_B is both a duplicate value and unreferenced — dead too.
        assert "dead-kind:F_B" in found

    def test_redeclared_kind_outside_registry(self, found):
        f = found["redeclared-kind:wirepkg/consumer.py:F_D"]
        assert f.path == "wirepkg/consumer.py" and f.line == 3

    def test_duplicate_magic(self, found):
        f = found["dup-magic:wirepkg/consumer.py:MAGIC_TWO"]
        assert f.path == "wirepkg/consumer.py" and f.line == 4
        assert "MAGIC_ONE" in f.message

    def test_kind_literal_compare(self, found):
        f = found["kind-literal:wirepkg/consumer.py:2"]
        assert f.path == "wirepkg/consumer.py" and f.line == 15

    def test_dispatch_without_reject_path(self, found):
        f = found["no-reject-path:wirepkg/consumer.py:decode"]
        assert f.path == "wirepkg/consumer.py"
        # route() compares a literal, not an F_* name — no dispatch
        # finding for it, and nothing else unexpected fired.
        assert "no-reject-path:wirepkg/consumer.py:route" not in found
        assert len(found) == 7, sorted(found)

    def test_wire_plane_magic_declaration(self):
        repo = Repo(os.path.join(FIXTURES, "wire"), rel_dirs=("wirepkg",))
        found = _by_key(wire_registry.check(
            repo, net_path="wirepkg/net.py", allowed_dupes={},
            wire_plane=("wirepkg/consumer.py",)))
        assert "wire-plane-magic:wirepkg/consumer.py:MAGIC_TWO" in found

    def test_allowed_dupe_suppresses_and_guards_drift(self):
        repo = Repo(os.path.join(FIXTURES, "wire"), rel_dirs=("wirepkg",))
        allow = {b"TSTA": {
            "files": frozenset({"wirepkg/net.py", "wirepkg/consumer.py"}),
            "reason": "fixture"}}
        found = _by_key(wire_registry.check(
            repo, net_path="wirepkg/net.py", allowed_dupes=allow,
            wire_plane=()))
        assert not any(k.startswith("dup-magic:") for k in found)
        # Drift guard: an allowed file that stops declaring the value.
        allow2 = {b"TSTB": {
            "files": frozenset({"wirepkg/net.py"}), "reason": "fixture"}}
        found2 = _by_key(wire_registry.check(
            repo, net_path="wirepkg/net.py", allowed_dupes=allow2,
            wire_plane=()))
        assert any(k.startswith("dupe-drift:wirepkg/net.py")
                   for k in found2)


class TestConfigCoverageChecker:
    @pytest.fixture()
    def found(self):
        repo = Repo(os.path.join(FIXTURES, "config_cov"),
                    rel_dirs=("confpkg",))
        return _by_key(config_coverage.check(
            repo, config_path="confpkg/config.py",
            doc_text="actor.num_actors and actor.documented_knob"))

    def test_ghost_attribute_read(self, found):
        f = found["ghost:actor.ghost_knob"]
        assert f.path == "confpkg/reader.py" and f.line == 6

    def test_ghost_getattr_read(self, found):
        f = found["ghost:actor.ghost_via_getattr"]
        assert f.path == "confpkg/reader.py" and f.line == 7

    def test_undocumented_knob(self, found):
        f = found["undocumented:actor.ghost_target"]
        assert f.path == "confpkg/config.py" and f.line == 11

    def test_declared_and_documented_reads_are_clean(self, found):
        assert "ghost:actor.num_actors" not in found
        assert "undocumented:actor.num_actors" not in found
        assert len(found) == 3


class TestMetricsDocChecker:
    def test_undocumented_names_fire_documented_dont(self):
        repo = Repo(os.path.join(FIXTURES, "metrics"),
                    rel_dirs=("metricspkg",))
        found = _by_key(metrics_doc.check(
            repo, doc_text="the doc mentions `good/counter` only"))
        g = found["instrument:bad/undocumented_gauge"]
        assert g.path == "metricspkg/bad_metrics.py" and g.line == 6
        s = found["section:ghost_section"]
        assert s.line == 7
        assert "instrument:good/counter" not in found
        assert len(found) == 2

    def test_doc_section_keys_parses_real_doc(self):
        keys = metrics_doc.doc_section_keys("## Supervisor schema")
        assert "respawns" in keys and "watchdog" in keys


class TestShmDisciplineChecker:
    def test_raw_create_fires_attach_does_not(self):
        repo = Repo(os.path.join(FIXTURES, "shm"), rel_dirs=("shmpkg",))
        found = shm_discipline.check(repo, blessed="elsewhere.py")
        assert len(found) == 1
        f = found[0]
        assert f.checker == "shm-discipline"
        assert f.path == "shmpkg/raw_shm.py" and f.line == 7
        assert f.key == "raw-create:shmpkg/raw_shm.py:make"

    def test_blessed_module_is_exempt(self):
        repo = Repo(os.path.join(FIXTURES, "shm"), rel_dirs=("shmpkg",))
        assert shm_discipline.check(
            repo, blessed="shmpkg/raw_shm.py") == []


class TestTypedErrorsChecker:
    def test_bare_and_unjustified_fire_justified_and_narrow_dont(self):
        repo = Repo(os.path.join(FIXTURES, "errors"), rel_dirs=("errpkg",))
        found = _by_key(typed_errors.check(repo, dirs=("errpkg",)))
        b = found["bare-except:errpkg/bad_except.py:decode:0"]
        assert b.line == 8
        s = found["silent-swallow:errpkg/bad_except.py:cleanup:0"]
        assert s.line == 15
        assert len(found) == 2, sorted(found)

    def test_out_of_scope_dirs_are_ignored(self):
        repo = Repo(os.path.join(FIXTURES, "errors"), rel_dirs=("errpkg",))
        assert typed_errors.check(repo, dirs=("otherdir",)) == []


# ---------------------------------------------------------------------------
# Baseline protocol.
# ---------------------------------------------------------------------------


class TestBaselineProtocol:
    def test_reasonless_entry_rejected(self, tmp_path):
        p = tmp_path / "baseline.json"
        p.write_text(json.dumps(
            {"entries": [{"checker": "x", "key": "y", "reason": "  "}]}))
        with pytest.raises(ValueError, match="no reason"):
            analysis.load_baseline(str(p))

    def test_suppression_and_stale_reporting(self, tmp_path):
        f1 = analysis.Finding("c", "a.py", 1, "k1", "m1")
        f2 = analysis.Finding("c", "b.py", 2, "k2", "m2")
        p = tmp_path / "baseline.json"
        p.write_text(json.dumps({"entries": [
            {"checker": "c", "key": "k1", "reason": "known-WAI"},
            {"checker": "c", "key": "gone", "reason": "fixed long ago"},
        ]}))
        result = analysis.apply_baseline(
            [f1, f2], analysis.load_baseline(str(p)))
        assert [f.key for f in result.new] == ["k2"]
        assert [f.key for f in result.suppressed] == ["k1"]
        assert [e["key"] for e in result.stale_baseline] == ["gone"]
        assert not result.ok

    def test_committed_baseline_loads_and_every_entry_has_reason(self):
        analysis.load_baseline()        # raises on a malformed commit


# ---------------------------------------------------------------------------
# The repo itself: the pytest twin of verify gate 14.
# ---------------------------------------------------------------------------


class TestRepoIsClean:
    def test_committed_tree_lints_clean(self):
        repo = Repo(REPO)
        findings = analysis.run_all(repo)
        result = analysis.apply_baseline(findings, analysis.load_baseline())
        assert result.ok, "NEW lint findings:\n" + "\n".join(
            f.render() for f in result.new)

    def test_cli_json_mode_clean_and_fast(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.lint", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["ok"] is True
        assert payload["files_scanned"] > 50

    @pytest.mark.parametrize("module", [
        m for m in IMPORT_LIGHT_CONTRACT])
    def test_contracted_module_is_dynamically_jax_free(self, module):
        """The runtime twin of the static walk: import each contracted
        module in a fresh interpreter and assert no heavy lib loaded."""
        code = (
            "import sys, importlib; "
            f"importlib.import_module({module!r}); "
            "heavy = [m for m in ('jax', 'jaxlib', 'flax', 'optax') "
            "if m in sys.modules]; "
            "assert not heavy, f'heavy imports loaded: {heavy}'"
        )
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, (module, proc.stderr)


# ---------------------------------------------------------------------------
# Doc-schema pins absorbed from test_obs.py: cheap stats dicts compared
# against docs/METRICS.md via the analyzer's shared parser.  (The pins
# needing a live training run stay in test_obs.py / test_replay_svc.py /
# test_central_inference.py, on the same parser.)
# ---------------------------------------------------------------------------


class TestDocSchemaDicts:
    def test_net_section_matches_doc(self):
        from ape_x_dqn_tpu.runtime.net import NetTransport

        doc = metrics_doc.doc_section_keys("## Net transport schema")
        assert doc, "Net transport schema doc section missing"
        tr = NetTransport()
        try:
            stats = tr.stats()
        finally:
            tr.close()
        assert set(doc) == set(stats), set(doc) ^ set(stats)

    def test_serving_net_section_matches_doc(self):
        from ape_x_dqn_tpu.serving.net_server import ServingNetServer

        class _Stub:
            param_version = 0

            def submit(self, obs):
                raise AssertionError("never called")

        doc = metrics_doc.doc_section_keys("## Serving net schema")
        assert doc, "Serving net schema doc section missing"
        srv = ServingNetServer(_Stub())
        try:
            stats = srv.stats()
        finally:
            srv.close()
        assert set(doc) == set(stats), set(doc) ^ set(stats)

    def test_serving_router_section_matches_doc(self):
        from ape_x_dqn_tpu.serving.router import ServingRouter

        doc = metrics_doc.doc_section_keys("## Serving router schema")
        assert doc, "Serving router schema doc section missing"
        router = ServingRouter(port=0)
        try:
            stats = router.stats()
        finally:
            router.close()
        assert set(doc) == set(stats), set(doc) ^ set(stats)

    def test_replay_tier_section_matches_doc(self, tmp_path):
        import numpy as np

        from ape_x_dqn_tpu.replay.dedup import DedupReplay
        from ape_x_dqn_tpu.types import DedupChunk

        doc = metrics_doc.doc_section_keys("## Replay tier schema")
        assert doc, "Replay tier schema doc section missing"
        rep = DedupReplay(64, (6, 6, 1), hot_frame_budget_bytes=128,
                          spill_dir=str(tmp_path), spill_span_frames=4)
        r = np.random.default_rng(0)
        rep.add(
            (np.abs(r.normal(size=8)) + 0.1).astype(np.float32),
            DedupChunk(
                frames=r.integers(0, 255, (9, 6, 6, 1), dtype=np.uint8),
                obs_ref=np.arange(8, dtype=np.int32),
                next_ref=np.arange(1, 9, dtype=np.int32),
                action=r.integers(0, 3, 8).astype(np.int32),
                reward=r.normal(size=8).astype(np.float32),
                discount=np.full(8, 0.9, np.float32),
                source=1, chunk_seq=0, prev_frames=9,
            ),
        )
        rep.spill_cold()
        rep.sample(8, rng=np.random.default_rng(1))  # faults cold spans
        stats = rep.tier_stats()
        assert stats["fault_reads"] > 0
        assert set(doc) == set(stats), set(doc) ^ set(stats)
        for key in ("count", "mean_ms", "p50_ms", "p95_ms", "p99_ms",
                    "max_ms"):
            assert key in stats["fault_ms"], key
