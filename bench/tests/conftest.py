"""Tests of the benchmark itself, run by path:

    python -m pytest bench/tests

They run on the CPU and are not part of the repository's tier-1 suite.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402


@pytest.fixture
def tiny():
    """``load_cell(name)`` cut to a volume the CPU runs in seconds: the
    same configuration, code path and unit kinds (first, interior and
    last block), Pallas in interpret mode."""
    from bench.run import load_cell

    def make(name):
        loaded = load_cell(name)
        job = loaded["job"]
        resident = job["cache_bytes"] > 0
        job.update(shape=[96, 32, 128], ndiv=3, region=[16, 16, 16],
                   cache_bytes=96 * 32 * 128 * 4 * 3 if resident else 0)
        return loaded

    return make
