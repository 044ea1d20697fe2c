"""``decompress(c)``: the fixed-rate decode of one unit. It reads the
packed payload and the per-block exponents and writes the float field.
Its operations are integer bit work, not counted."""

from __future__ import annotations

from bench.work import io_bytes


def work(call):
    return {"bytes": io_bytes(call), "ops": None}
