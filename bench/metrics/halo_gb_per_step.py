"""Bytes that crossed between shards in the window, per time step: the
held slices and the encoded unit halos, as the sharded executor's own
``halo_wire`` counter (``transfer_summary()``) has them, in GB (1e9
bytes). None for an engine with no halo."""


def read(record):
    t, steps = record.get("transfers"), record.get("steps", 0)
    if not t or not t.get("halo_wire") or not steps:
        return None
    return t["halo_wire"] / steps / 1e9
