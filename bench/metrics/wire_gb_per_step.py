"""Bytes that crossed the host link in the window, both directions,
per time step: the executor's own transfer counters
(``transfer_summary()``), in GB (1e9 bytes)."""


def read(record):
    t, steps = record.get("transfers"), record.get("steps", 0)
    if not t or not steps:
        return None
    return (t["h2d_wire"] + t["d2h_wire"]) / steps / 1e9
