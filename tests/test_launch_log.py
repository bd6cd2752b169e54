"""The launch log (``utils/profiling.LaunchLog``): what it records, what its
reduction adds up to, and where a reader finds it.  CPU only."""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.utils import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def log(monkeypatch):
    """A fresh log in the place of the process's, with jax's compile events
    feeding it."""
    fresh = profiling.LaunchLog()
    monkeypatch.setattr(profiling, "launch", fresh)
    profiling.listen()
    return fresh


def _made(rows, t0=0.0, thread=None):
    """A log holding hand-made rows ``(name, start, end[, attrs[, thread]])``
    on a clock that starts at ``t0``."""
    log = profiling.LaunchLog()
    log._t0 = t0
    for name, s, e, *rest in rows:
        attrs = rest[0] if rest else {}
        on = rest[1] if len(rest) > 1 else log.thread
        log._rows.append([name, s, e, None, on, attrs])
    return log


HIT = {"program": "f", "cache": "hit", "retrieval_s": 0.25}
MISS = {"program": "g", "cache": "miss"}
NESTS = {
    # a builder that holds a trace that holds another program's trace
    "nested": (
        [("import:jax", 0.0, 3.0), ("import:numpy", 0.5, 1.5),
         ("backend", 3.0, 4.0), ("network", 4.0, 9.0),
         ("compile:trace", 5.0, 7.0, {"program": "f"}),
         ("compile:trace", 5.5, 6.0, {"program": "g"}),
         ("compile:lower", 7.0, 7.5, {"program": "f"}),
         ("compile:backend", 7.5, 8.5, HIT),
         ("apex:force_oldest", 9.5, 10.0)],
        10.0,
        dict(import_s=3.0, chip_start_s=1.0, trace_s=2.0, lower_s=0.5,
             cache_load_s=1.0, compile_s=0.0, build_s=1.5, device_wait_s=0.5,
             unattributed_s=0.5)),
    # a span that outlasts the one it starts in ends with it; a miss is
    # compile_s; another thread's compile is in `programs` and in no part
    "overhang_and_threads": (
        [("train_state", 1.0, 4.0),
         ("compile:backend", 3.0, 5.0, MISS),
         ("compile:backend", 0.0, 6.0, MISS, -7)],
        6.0,
        dict(import_s=0.0, chip_start_s=0.0, trace_s=0.0, lower_s=0.0,
             cache_load_s=0.0, compile_s=1.0, build_s=2.0, device_wait_s=0.0,
             unattributed_s=3.0)),
    # no `backend` span: the uncovered time from import:jax to the mark
    "chip_start_between_marks": (
        [("import:jax", 0.0, 2.0), ("import:flax", 2.5, 3.0),
         ("compile_cache", 5.0, 5.0), ("network", 5.0, 6.0)],
        8.0,
        dict(import_s=2.5, chip_start_s=2.5, trace_s=0.0, lower_s=0.0,
             cache_load_s=0.0, compile_s=0.0, build_s=1.0, device_wait_s=0.0,
             unattributed_s=2.0)),
}


@pytest.mark.parametrize("nest", sorted(NESTS))
def test_a_nest_of_spans_partitions_exactly_and_by_self_time(nest):
    rows, t1, want = NESTS[nest]
    s = _made(rows).summary(t1=t1)
    got = {p: s[p] for p in profiling.LAUNCH_PARTS}
    assert got == pytest.approx(want, abs=1e-12)
    assert sum(got.values()) == pytest.approx(s["seconds"], abs=1e-12)
    assert s["seconds"] == t1
    said = " ".join(s["notes"])
    assert ("two marks" in said) == (nest == "chip_start_between_marks")
    assert ("not the program's" in said) == (nest != "nested")


def test_the_program_table_counts_every_thread_and_folds_the_rest():
    rows, t1, _ = NESTS["nested"]
    log = _made(rows + [("compile:backend", 1.0, 3.0, MISS, -7)])
    s = log.summary(t1=t1)
    assert s["programs"]["f"] == {
        "trace_s": 1.5, "lower_s": 0.5, "backend_s": 1.0, "cache": "hit",
        "retrieval_s": 0.25, "compiles": 1}
    assert s["programs"]["g"]["trace_s"] == 0.5
    assert s["programs"]["g"]["backend_s"] == 2.0    # the other thread's
    assert (s["cache_hits"], s["cache_misses"]) == (1, 1)
    assert s["spans"]["network"] == 1.5 and s["spans"]["import:jax"] == 2.0
    # half the interval: the spans are cut to it
    assert log.summary(t0=5.0, t1=7.5)["trace_s"] == 2.0
    folded = log.summary(t1=t1, top=1)["programs"]
    assert list(folded) == ["f", "(1 other programs)"]
    assert folded["(1 other programs)"]["compiles"] == 1


# The test's own threshold, forty times the package's: the first trace sleeps
# three times it, and a trace found again in jax's cache (14 us alone) stays
# under it on a machine whose other cores all compile.
FOLD_UNDER_S = 0.02


@pytest.mark.parametrize("fold_under", [0.0, FOLD_UNDER_S])
def test_an_inner_jit_traced_twenty_times_is_one_row_and_not_outers_time(
        log, monkeypatch, fold_under):
    @jax.jit
    def inner(x):
        time.sleep(3 * FOLD_UNDER_S)    # traced once: later calls find the jaxpr
        return jnp.sin(x) * 2

    def outer(x):
        time.sleep(0.05)
        for _ in range(20):
            x = inner(x)
        return x

    monkeypatch.setattr(profiling, "_FOLD_TRACE_S", fold_under)
    jax.jit(outer)(jnp.ones(3)).block_until_ready()
    traces = [r for r in log._rows if r[0] == "compile:trace"]
    # jax reports a trace span each time, the nineteen that find the jaxpr
    # too; those are short, and short ones are counted and not kept
    kept = sum(r[5]["program"] == "inner" for r in traces)
    assert (kept, log.folded >= 19) == ((20, False) if fold_under == 0 else (1, True))
    assert log.summary()["folded"] == log.folded
    outer_span = next(r for r in traces if r[5]["program"] == "outer")
    s = log.summary()
    row = s["programs"]["outer"]
    assert row["compiles"] == 1 and s["programs"]["inner"]["compiles"] == 0
    inner_s = s["programs"]["inner"]["trace_s"]
    assert inner_s >= 0.002
    # outer's own trace time leaves out what inner's spans (and what they
    # hold) cover
    assert 0.05 <= row["trace_s"] <= outer_span[2] - outer_span[1] - inner_s + 2e-6
    assert row["lower_s"] > 0 and row["backend_s"] > 0
    assert sum(s[p] for p in profiling.LAUNCH_PARTS) == pytest.approx(
        s["seconds"], abs=1e-9)


TWO_PROCESSES = """
import json
import ape_x_dqn_tpu
from ape_x_dqn_tpu.utils import profiling
from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache
import jax, jax.numpy as jnp
enable_compile_cache()

def only_here(x):
    return jnp.tanh(x) @ x.T + 3

jax.jit(only_here)(jnp.ones((64, 64))).block_until_ready()
s = profiling.launch.summary()
print(json.dumps({"row": s["programs"]["only_here"], "compile_s": s["compile_s"],
                  "cache_load_s": s["cache_load_s"], "hits": s["cache_hits"],
                  "misses": s["cache_misses"], "import_jax": s["spans"]["import:jax"],
                  "chip_start_s": s["chip_start_s"]}))
"""


def test_two_processes_on_one_cache_directory_read_a_miss_then_a_hit(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop(profiling.LAUNCH_LOG_ENV, None)
    said = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", TWO_PROCESSES], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        said.append(json.loads(p.stdout.strip().splitlines()[-1]))
    cold, warm = said
    assert cold["row"]["cache"] == "miss" and cold["row"]["retrieval_s"] == 0
    assert cold["compile_s"] > 0 and cold["misses"] > 0 and cold["hits"] == 0
    assert warm["row"]["cache"] == "hit" and warm["row"]["retrieval_s"] > 0
    assert warm["compile_s"] == 0 and warm["misses"] == 0
    assert warm["cache_load_s"] >= warm["row"]["retrieval_s"] > 0
    # the entry point was not the program's: jax's import was timed by the
    # finder, the chip's start-up read between the two marks
    assert cold["import_jax"] > 0 and cold["chip_start_s"] > 0
    assert not list(tmp_path.glob("*.json"))


def test_the_finder_times_a_module_once_and_lets_an_import_error_through(
        tmp_path, monkeypatch, log):
    (tmp_path / "launch_stub.py").write_text("import time\ntime.sleep(0.01)\nX = 1\n")
    (tmp_path / "launch_stub_broken.py").write_text(
        "raise ImportError('no such backend', name='launch_stub_broken')\n")
    asked = []

    class Counting(profiling._ImportTimer):
        def find_spec(self, fullname, path=None, target=None):
            asked.append(fullname)
            return super().find_spec(fullname, path, target)

    names = {"launch_stub", "launch_stub_broken", "launch_stub_missing"}
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setattr(sys, "meta_path", [Counting(names)] + sys.meta_path)
    try:
        stub = importlib.import_module("launch_stub")
        assert importlib.import_module("launch_stub") is stub
        assert asked.count("launch_stub") == 1      # a hit in sys.modules asks no finder
        spans = [r for r in log._rows if r[0] == "import:launch_stub"]
        assert len(spans) == 1 and spans[0][2] - spans[0][1] >= 0.01
        # the module keeps its own loader
        assert not isinstance(stub.__spec__.loader, profiling._TimedLoader)
        assert not isinstance(stub.__loader__, profiling._TimedLoader)
        assert stub.__spec__.loader.get_source("launch_stub").endswith("X = 1\n")
        with pytest.raises(ModuleNotFoundError) as missing:
            importlib.import_module("launch_stub_missing")
        assert missing.value.name == "launch_stub_missing"
        with pytest.raises(ImportError, match="no such backend") as broken:
            importlib.import_module("launch_stub_broken")
        assert type(broken.value) is ImportError
        closed = [r for r in log._rows if r[0] == "import:launch_stub_broken"]
        assert len(closed) == 1 and closed[0][2] is not None
        assert log._stack() == []
    finally:
        for name in names:
            sys.modules.pop(name, None)


def test_the_package_puts_the_finder_first_and_stays_off_jax():
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys, ape_x_dqn_tpu\n"
         "from ape_x_dqn_tpu.utils import profiling\n"
         "assert isinstance(sys.meta_path[0], profiling._ImportTimer)\n"
         "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
         "import numpy\n"
         "assert [r[0] for r in profiling.launch._rows] == ['import:numpy']\n"],
        env=dict(os.environ, PYTHONPATH=ROOT), capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_a_compile_after_done_is_a_recompile_with_its_program_and_step(log):
    def first(x):
        return x * 2 + 1

    def again(x):
        return x * 3 - 1

    jax.jit(first)(jnp.ones(5)).block_until_ready()
    assert log.done(7) and not log.done(8)
    before = len(log._rows)
    log.step = 9
    jax.jit(again)(jnp.ones(5)).block_until_ready()
    since = log.since_launch()
    assert since["compiles_after_launch"] >= 1
    mine = [r for r in since["recompiles"] if r["program"] == "again"]
    assert len(mine) == 1 and mine[0]["step"] == 9 and mine[0]["seconds"] > 0
    assert mine[0]["thread"] == threading.current_thread().name
    assert mine[0]["cache"] in ("hit", "miss", "off")
    # the launch's own record is closed: nothing joins it
    assert len(log._rows) == before
    s = log.summary()
    assert s["done"] and s["step"] == 7 and "again" not in s["programs"]
    assert log.varz()["recompiles"] == since["recompiles"]
    # a later launch of the same process starts where `begin` is called
    log.begin()
    assert log.since_launch() == {"compiles_after_launch": 0, "recompiles": []}
    assert not log.summary()["done"] and log.summary()["seconds"] < s["seconds"] + 60
    with log.span("network"):
        pass
    assert log._rows[-1][0] == "network" and log.done(1)


def test_stage_timer_and_launch_span_share_one_implementation(log, monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    through = []
    real = profiling.LaunchLog._span

    def counted(self, name, annotation, attrs=None, closed=None):
        through.append(name)
        return real(self, name, annotation, attrs, closed)

    monkeypatch.setattr(profiling.LaunchLog, "_span", counted)
    timers = profiling.StageTimer()
    with log.span("ring_make", rows=4):
        with timers.stage("ingest"):
            pass
    assert through == ["ring_make", "apex:ingest"]
    assert entered == ["apex:launch:ring_make", "apex:ingest"]
    outer, inner = log._rows
    assert outer[0] == "ring_make" and outer[5] == {"rows": 4} and outer[3] is None
    assert inner[0] == "apex:ingest" and inner[3] == 0      # its parent: the open span
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    log.done(0)
    with timers.stage("ingest"):       # counted as before, no longer recorded
        pass
    assert len(log._rows) == 2 and entered[-1] == "apex:ingest"
    assert set(timers.us_per_call()) == {"ingest"} and timers._count["ingest"] == 2


@pytest.mark.parametrize("named", [False, True])
def test_the_log_is_written_at_exit_only_where_the_variable_names_a_file(
        tmp_path, monkeypatch, capsys, log, named):
    monkeypatch.chdir(tmp_path)
    if named:
        monkeypatch.setenv(profiling.LAUNCH_LOG_ENV, str(tmp_path / "out" / "l-{pid}.json"))
    else:
        monkeypatch.delenv(profiling.LAUNCH_LOG_ENV, raising=False)
    with log.span("network", kind="conv"):
        jax.jit(lambda x: x + 41)(jnp.ones(2)).block_until_ready()
    with log.span("gather_path", path="kernel", rows=512, words=7168):
        pass
    assert log.attrs_of("gather_path") == [{"path": "kernel", "rows": 512, "words": 7168}]
    open_row = log._append("pipeline", time.perf_counter(), None, None)
    profiling._write_at_exit()
    written = [str(p.relative_to(tmp_path)) for p in tmp_path.rglob("*") if p.is_file()]
    if not named:
        assert written == []
        return
    assert written == [f"out/l-{os.getpid()}.json"]
    back = profiling.LaunchLog.from_file(tmp_path / written[0])
    assert back.pid == os.getpid() and back.thread == log.thread
    assert [r[0] for r in back._rows] == [r[0] for r in log._rows]
    assert back._rows[open_row][2] == back._written      # open at the end: it lasts until then
    assert back.summary() == log.summary(t1=back._written)
    from tools import launch_report

    assert launch_report.main([str(tmp_path / written[0]), "--seconds", "0.5"]) == 0
    assert "gather_path: kernel, 512 rows of 7168 words" in capsys.readouterr().out


TOY = ["--set", "network=conv", "--set", "env.name=fake-atari",
       "--set", "learner.device_replay=true", "--set", "learner.sample_ahead=true",
       "--set", "replay.capacity=1024", "--set", "learner.steps_per_call=2",
       "--set", "actor.num_actors=4", "--set", "learner.min_replay_mem_size=64",
       "--set", "learner.replay_sample_size=8", "--set", "learner.publish_every=1000000",
       "--steps", "8", "--log-every", "2"]


def test_a_toy_trainer_writes_one_launch_event_and_reports_a_recompile(
        tmp_path, log, capsys):
    from ape_x_dqn_tpu import train
    from ape_x_dqn_tpu.replay.device import init_device_replay

    seen = {}

    def inspect(pipe, final):
        seen["varz"] = pipe.obs_registry.snapshot()["launch"]
        # the fused program once more, over a ring of another size: a new
        # shape, so it compiles again, after the launch
        fused = pipe.fused

        def grown(old, new):  # on the host: no program but the fused one
            out = np.array(new)
            out[tuple(slice(0, n) for n in old.shape)] = np.asarray(old)
            return jax.device_put(out)

        fused._replay = jax.tree_util.tree_map(
            grown, fused._replay, init_device_replay(2048, pipe.comps.obs_shape))
        log.recompiles.clear()      # what building the larger ring compiled
        log.compiles_after_launch = 0
        seen["step"] = pipe.learner_step
        metrics = fused.train(0.4)
        seen["after"] = pipe._emit_fused(metrics)
        seen["varz_after"] = pipe.obs_registry.snapshot()["launch"]

    path = tmp_path / "metrics.jsonl"
    assert train.main(TOY + ["--metrics-file", str(path)], inspect=inspect) == 0
    capsys.readouterr()
    records = [json.loads(line) for line in path.read_text().splitlines()]
    events = [r for r in records if r.get("event") == "launch"]
    assert len(events) == 1
    ev = events[0]
    assert ev["done"] and ev["step"] == 2 and ev["pid"] == os.getpid()
    assert sum(ev[p] for p in profiling.LAUNCH_PARTS) == pytest.approx(
        ev["seconds"], abs=1e-6)
    assert ev["device_wait_s"] > 0 and ev["build_s"] > 0 and ev["trace_s"] > 0
    assert "fused_no_ingest" in ev["programs"] and len(ev["programs"]) <= 13
    for name in ("pipeline", "components", "network", "train_state",
                 "fused_learner", "ring_make", "fused_program", "ring_fill",
                 "backend", "apex:force_oldest", "apex:fused_dispatch"):
        assert name in ev["spans"], name
    # before the recompile: every periodic record and /varz say none
    periodic = [r for r in records if "step" in r and "event" not in r]
    assert periodic[0]["launch"] == {"compiles_after_launch": 0, "recompiles": []}
    assert seen["varz"]["compiles_after_launch"] == 0
    assert seen["varz"]["seconds"] == ev["seconds"]
    # after it: the JSONL line and /varz name the program and the step
    for said in (seen["after"]["launch"], seen["varz_after"]):
        assert said["compiles_after_launch"] == 1
        assert said["recompiles"] == [dict(
            said["recompiles"][0], program="fused_no_ingest", step=seen["step"])]
    assert records[-1]["launch"]["compiles_after_launch"] == 1

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import obs_top
    finally:
        sys.path.pop(0)
    frame = obs_top.render(obs_top.snapshot_from_jsonl(str(path)))
    assert "-- launch" in frame and "compiles_after_launch 1" in frame
    assert "recompile  fused_no_ingest" in frame
