"""Host time exchanging the halo between shards per time step, in ms:
the union of the engine's ``ooc.halo.held`` (a held slice handed to the
right neighbour) and ``ooc.halo.unit`` (an encoded unit put through the
left neighbour's host store, its d2h and crc32 inside) spans in the
window."""

from bench.spans import ms_per_step


def read(record):
    return ms_per_step(record, ["ooc.halo.held", "ooc.halo.unit"])
