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
    last block), Pallas in interpret mode.

    ``shards=N`` makes it a sharded job on a cell of N chips: two
    24-plane blocks a shard of 8 (the engine refuses blocks of 16
    planes, no wider than twice its halo of 8), and three rounds before
    the check, so that a halo one round stale shows above the limits."""
    from bench.run import load_cell

    def make(name, shards=1):
        loaded = load_cell(name)
        job = loaded["job"]
        if shards > 1:
            job.update(shape=[192, 32, 128], ndiv=8, region=[16, 16, 16],
                       shards=shards, warm_rounds=2)
            loaded["cell"] = dict(loaded["cell"], chips=shards)
            return loaded
        resident = job["cache_bytes"] > 0
        job.update(shape=[96, 32, 128], ndiv=3, region=[16, 16, 16],
                   cache_bytes=96 * 32 * 128 * 4 * 3 if resident else 0)
        return loaded

    return make
