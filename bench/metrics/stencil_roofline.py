"""Share of the HBM roofline reached by the stencil program
``fused_temporal_steps`` in the window (device trace)."""

from bench.roofline import share
from bench.work.stencil import work


def read(record):
    return share(record, "fused_temporal_steps", work)
