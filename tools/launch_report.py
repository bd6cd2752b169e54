"""launch_report — read a launch log a process wrote at its exit.

    APEX_LAUNCH_LOG=launch.json python3 benchmark/run.py --workload ... --trace 0
    python3 tools/launch_report.py launch.json --seconds <the run's printed set-up>

A process that ran with ``APEX_LAUNCH_LOG`` set leaves its launch log there
(``ape_x_dqn_tpu/utils/profiling.py``: imports, chip start-up, builders, every
compile by program and phase).  This prints the partition of the log's first
``--seconds`` seconds (default: up to the launch's ``done``, or to the
writing) into the nine parts of ``LaunchLog.summary``, the launch thread's
own seconds by span, the table of programs, slowest first, and which path
each traced side of the dedup ring's gather stage took (``gather_path``:
the kernel or the compiler's gather).  The log's
first stamp is the package's import: what the process did before it (the
interpreter's start, the entry point's own first imports) is not in it.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ape_x_dqn_tpu.utils import profiling  # noqa: E402  (standard library only)


PROGRAMS = 12  # rows of the program table, before one for the rest


def report(summary: dict) -> str:
    total = summary["seconds"]
    lines = [f"launch of pid {summary['pid']}: {total:.3f} s"
             + (f", done at step {summary['step']}" if summary["done"] else "")]
    for part in profiling.LAUNCH_PARTS:
        lines.append(f"  {part:<16}{summary[part]:>10.3f} s"
                     f"{100 * summary[part] / total if total else 0:>7.1f} %")
    lines.append(f"  {'(sum)':<16}"
                 f"{sum(summary[p] for p in profiling.LAUNCH_PARTS):>10.3f} s")
    lines.append(f"  cache hits {summary['cache_hits']}, misses "
                 f"{summary['cache_misses']}, compiles after the launch "
                 f"{summary['compiles_after_launch']}, spans dropped "
                 f"{summary['dropped']}, short trace spans folded "
                 f"{summary['folded']}")
    lines += [f"  note: {note}" for note in summary["notes"]]
    lines.append("own seconds by span (launch thread):")
    lines += [f"  {name:<48}{own:>10.3f}"
              for name, own in summary["spans"].items() if own >= 0.0005]
    lines.append(f"{'program':<40}{'trace_s':>9}{'lower_s':>9}{'backend_s':>10}"
                 f"{'retrieval_s':>12}{'compiles':>9}  cache")
    for name, p in summary["programs"].items():
        lines.append(f"{name[:39]:<40}{p['trace_s']:>9.3f}{p['lower_s']:>9.3f}"
                     f"{p['backend_s']:>10.3f}{p['retrieval_s']:>12.3f}"
                     f"{p['compiles']:>9}  {p['cache']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="launch_report")
    ap.add_argument("file", help="what APEX_LAUNCH_LOG named")
    ap.add_argument("--seconds", type=float, default=None,
                    help="partition the log's first SECONDS seconds "
                    "(a benchmark run's printed setup_s)")
    args = ap.parse_args(argv)
    log = profiling.LaunchLog.from_file(args.file)
    t0 = log.created[0]
    summary = log.summary(
        t0=t0, t1=None if args.seconds is None else t0 + args.seconds,
        top=PROGRAMS)
    print(report(summary))
    for side in log.attrs_of("gather_path"):  # replay/device_dedup.dedup_fetch, a traced side
        print(f"gather_path: {side['path']}, {side['rows']} rows of {side['words']} words")
    for walk in log.attrs_of("scan_path"):    # ops/chunked_delta.chunked_delta, a traced layer kind
        print(f"scan_path: {walk['path']}, {walk['heads']} heads, keys of {walk['key']}, "
              f"values of {walk['value']}, the inverse {walk['inverse']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
