"""bench.py without a chip: the device sections are what a plain
``python bench.py`` asks for, so with no TPU it must exit non-zero, before
any compile, and print no result line — in particular no
``"value": null`` line that a reader could take for a measurement.  (It
used to print exactly that and exit 0; the host-only sub-commands the
verify gates use stay host-only and are covered by the gates.)"""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode != 0, proc.stdout[-500:]
    assert "need a TPU" in proc.stderr, proc.stderr[-2000:]
    assert '"value"' not in proc.stdout, proc.stdout[-500:]
    assert '"metric"' not in proc.stdout, proc.stdout[-500:]
    # Refused at the device check, not after minutes of host sections.
    assert time.monotonic() - t0 < 60
