"""Run by path, not by the repository's tier-1 suite:

    python -m pytest -q benchmarks/chip/tests

Four virtual CPU devices stand in for the 4-chip mesh; the flag must be
set before JAX first initialises.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent.parent
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
