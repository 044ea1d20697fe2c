"""``compress(x, planes=p)``: the fixed-rate encode of one unit. It
reads the float field and writes the packed payload and the per-block
exponents. Its operations are integer bit work, not counted."""

from __future__ import annotations

from bench.work import io_bytes


def work(call):
    return {"bytes": io_bytes(call), "ops": None}
