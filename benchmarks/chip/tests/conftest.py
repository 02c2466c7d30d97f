"""The chip benchmark's CPU tests: the harness, its readers and references
at sizes a test can hold.  Like ``tests/conftest.py``, ask the CPU for 8
devices before JAX starts, so the four-rank cell runs on virtual devices."""
import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
