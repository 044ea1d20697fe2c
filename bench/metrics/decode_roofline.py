"""Share of the HBM roofline reached by the codec's decode program
``decompress`` in the window (device trace)."""

from bench.roofline import share
from bench.work.decode import work


def read(record):
    return share(record, "decompress", work)
