"""Tests of the benchmark's own code (``benchmark/``): on the CPU, at toy
sizes.  tests/conftest.py has already held JAX to virtual CPU devices."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
