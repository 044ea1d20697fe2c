"""Host time digesting units per time step, in ms: the union of the
engine's ``ooc.store.checksum`` spans (every crc32 digest of the host
store, read in place, a large part in chunks on a pool of threads
whose wait is inside the span) in the window."""

from bench.spans import ms_per_step


def read(record):
    return ms_per_step(record, ["ooc.store.checksum"])
