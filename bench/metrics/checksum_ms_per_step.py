"""Host time digesting units per time step, in ms: the union of the
engine's ``ooc.store.checksum`` spans (every crc32 of the host store,
the ``.tobytes()`` copy included) in the window."""

from bench.spans import ms_per_step


def read(record):
    return ms_per_step(record, ["ooc.store.checksum"])
