"""trace_reduce against one small xplane recorded on a TPU v5e (PR 25) with the
options run.py traces with: five runs of a K=8 fused learner at toy batch, two
in flight, under bench:ingest / bench:dispatch / bench:force annotations, with
one 3 ms sleep on the host; and against hand-made events."""
import os

import pytest

import trace_reduce as tr
from trace_reduce import Event

XPLANE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small_tpu.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(XPLANE)


def test_planes_and_spans(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    dev = trace.devices["/device:TPU:0"]
    assert len(dev.ops) > 1000 and len(dev.modules) >= 3
    names = [s.name for s in trace.spans]
    assert [names.count("bench:" + n) for n in ("ingest", "dispatch", "force")] == [5, 5, 5]


def test_window_busy_and_idle(trace):
    t0, t1 = tr.span_window(trace)
    forces = [s for s in trace.spans if s.name == "bench:force"]
    assert t0 == forces[0].end and t1 == forces[-1].end
    s = tr.summarize(trace)
    assert s["window_s"] == pytest.approx(t1 - t0)
    assert 0 < s["busy_s"] < s["window_s"]
    # Three whole 1.16 ms runs of the fused program lie in the 13.6 ms window;
    # at toy size the device is mostly idle.
    assert s["window_s"] == pytest.approx(13.567e-3, rel=1e-3)
    assert s["busy_s"] == pytest.approx(3.45e-3, rel=0.01)
    assert s["collective_s"] == 0.0


def test_module_seconds_counts_whole_runs(trace):
    t0, t1 = tr.span_window(trace)
    seconds, runs = tr.module_seconds(trace, "jit_fused", t0, t1)
    assert runs == 3 and seconds == pytest.approx(3 * 1.158e-3, rel=0.01)
    assert tr.module_seconds(trace, "jit_absent", t0, t1) == (0.0, 0)


def test_top_ops_are_own_time_and_gaps_are_named(trace):
    s = tr.summarize(trace)
    ops = dict(s["device_ops"])
    assert len(s["device_ops"]) == 10 and len(s["idle_gaps"]) <= 10
    # the scan's while op covers its body: its own time is not the whole scan
    assert ops.get("while.7", 0.0) < 0.2e-3  # inclusive, it would be about 2.8 ms
    assert sum(ops.values()) <= s["busy_s"] * 1.0001
    assert s["idle_gaps"][0][0] == "bench:force"  # the longest gap holds the sleep
    assert "host:unattributed" in [g[0] for g in s["idle_gaps"]]
    assert s["idle_gaps"][0][1] >= s["idle_gaps"][-1][1] > 0
    assert sum(g for _, g in s["idle_gaps"]) <= s["window_s"] - s["busy_s"] + 1e-9


def test_union_self_time_and_gaps_by_hand():
    evs = [Event("%while.1 = x", 0.0, 10.0), Event("%a = y", 1.0, 3.0),
           Event("%b = y", 3.0, 4.0), Event("%c = z", 12.0, 13.0)]
    assert tr.union(evs) == [(0.0, 10.0), (12.0, 13.0)]
    assert tr.busy_seconds(evs) == pytest.approx(11.0)
    assert tr.self_times(evs) == pytest.approx({"while.1": 7.0, "a": 2.0, "b": 1.0, "c": 1.0})
    assert tr.gaps(evs, -1.0, 14.0) == [(-1.0, 0.0), (10.0, 12.0), (13.0, 14.0)]
    spans = [Event("bench:force", 9.0, 11.5), Event("bench:dispatch", 11.5, 12.5)]
    assert tr.name_gap((10.0, 12.0), spans) == "bench:force"
    assert tr.name_gap((20.0, 21.0), spans) == "host:unattributed"
    assert tr.op_name("%fusion.3 = f32[2]{0} fusion(%p)") == "fusion.3"


def test_collectives_are_found_by_op_name():
    dev = tr.DeviceTrace(
        ops=[Event("%fusion.1 = f32[] fusion()", 1.0, 2.0),
             Event("%all-reduce.5 = f32[] all-reduce()", 2.0, 2.5)],
        async_ops=[Event("%all-gather-start.2 = f32[] all-gather-start()", 2.25, 3.0)],
        modules=[])
    spans = [Event("bench:force", 0.0, 1.0), Event("bench:force", 3.0, 4.0)]
    s = tr.summarize(tr.Trace({"/device:TPU:0": dev}, spans))
    assert s["window_s"] == pytest.approx(3.0)
    assert s["collective_s"] == pytest.approx(1.0)
    assert s["busy_s"] == pytest.approx(1.5)


def test_errors():
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace({}, []))
    with pytest.raises(ValueError):
        tr.span_window(tr.Trace({}, [Event("bench:force", 0.0, 1.0)]))
    with pytest.raises(FileNotFoundError):
        tr.find_xplane("/nonexistent")
