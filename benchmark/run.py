"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration, its traffic mix and the
mix's driver by name, runs the driver on the chips the cell asks for, checks
the program's outputs against the plain reference, and prints as the last
line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.

No accelerator, too few chips, or no program beside it: exit 2, no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest as mf  # noqa: E402
from spans import Spans  # noqa: E402


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def refuse(msg: str) -> int:
    print(f"benchmark/run.py: {msg}; nothing measured, no result", file=sys.stderr)
    return 2


class Context:
    """What a driver gets: the cell, the run's arguments, spans, a profiler
    and the count of compilations so far."""

    def __init__(self, cell, args, out_dir: str):
        self.cell = cell
        self.seed, self.seconds, self.trace = args.seed, float(args.seconds), bool(args.trace)
        self.spans = Spans()
        self.out_dir = out_dir
        self.setup_s = None
        self._compiles = 0
        import jax

        def on_event(name, *_a, **_k):
            if name.startswith("/jax/core/compile"):
                self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def compile_events(self) -> int:
        return self._compiles

    def mark_setup_done(self, at: float) -> None:
        self.setup_s = at - T_START

    @contextlib.contextmanager
    def profiler(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.spans.annotate = True
        try:
            yield
        finally:
            self.spans.annotate = False
            jax.profiler.stop_trace()


def live_peak_bytes(devs) -> int:
    return int(max(d.memory_stats()["peak_bytes_in_use"] for d in devs))


def measure(cell, args, devs, peaks) -> dict:
    """Run the cell's driver, decide ``correct``, read the metrics: the
    result object, ready to print."""
    import correctness
    import ops_count
    import trace_reduce

    ctx = Context(cell, args, os.path.join(ROOT, ".bench_out", "trace", cell.name))
    obs = cell.driver().run(ctx)
    say(f"set-up {ctx.setup_s:.3f} s; window {obs['window'][1] - obs['window'][0]:.3f} s; "
        f"counters {obs['counters']}")

    # -- correct: exact counters, then the comparison with the reference --
    ok = True
    for what, got, want in obs["exact_checks"]:
        good = got == want
        ok = ok and good
        say(f"compare {what} = {got}  expected {want}  {'ok' if good else 'FAIL'}")
    counts, numbers = obs["check"]()
    good, lines = correctness.verdict(
        counts, numbers, correctness.load_limits(cell.config_name, cell.bench_dir))
    ok = ok and good
    for line in lines:
        say(line)

    # The allocator's peak of live buffers leaves out a running program's
    # temporaries on this runtime, so the fused program's, by the compiler's
    # count, are added to it.
    live = live_peak_bytes(devs[: cell.chips])
    say(f"memory: live buffers peak {live} B + the fused program's temporaries "
        f"{obs['program_temp_bytes']} B")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": live + int(obs["program_temp_bytes"])}
    result = {"correct": bool(ok), "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"])}
    end_to_end = dict(obs["end_to_end"], setup_s=ctx.setup_s)
    metrics = {}
    if not args.trace:
        for m in cell.end_to_end():
            if m["name"] not in end_to_end:
                raise KeyError(f"driver {cell.traffic['driver']} measured no {m['name']}")
            metrics[m["name"]] = {"value": end_to_end[m["name"]], "unit": m["unit"]}
    else:
        raw = trace_reduce.load(trace_reduce.find_xplane(ctx.out_dir))
        summary = trace_reduce.summarize(raw)
        shutil.rmtree(ctx.out_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
        readings = types.SimpleNamespace(  # what a per-layer reader gets
            cell=cell, config=cell.config, spans=ctx.spans, window=obs["window"],
            counters=obs["counters"], end_to_end=end_to_end, trace=raw,
            trace_summary=summary, fused_program=obs["fused_program"],
            peaks=peaks, ops_count=ops_count, trace_reduce=trace_reduce, device=device,
        )
        for m in cell.per_layer():
            value = cell.reader(m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = device
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        import ape_x_dqn_tpu  # noqa: F401
        from ape_x_dqn_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        return refuse(f"the program is not beside the benchmark ({e})")
    cell = mf.Cell(mf.load_manifest(), args.workload)

    import jax

    if jax.default_backend() != "tpu":
        return refuse(f"needs a TPU, jax's default backend is {jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < cell.chips:
        return refuse(f"cell {cell.name} needs {cell.chips} chips, jax found {len(devs)}")
    cache = enable_compile_cache()
    from peaks import peaks_for

    peaks = peaks_for(devs[0].device_kind)
    say(f"cell={cell.name} config={cell.config_name} traffic={cell.traffic_name} "
        f"seed={args.seed} seconds={args.seconds} trace={args.trace} "
        f"device={devs[0].device_kind} x{len(devs)} compile_cache={cache}")
    result = measure(cell, args, devs, peaks)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
