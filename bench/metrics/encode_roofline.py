"""Share of the HBM roofline reached by the codec's encode program
``compress`` in the window (device trace)."""

from bench.roofline import share
from bench.work.encode import work


def read(record):
    return share(record, "compress", work)
