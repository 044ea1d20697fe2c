"""``fused_temporal_steps(p_prev, p_cur, vel2, steps=n)``: ``n`` steps
of the 25-point 8th-order update over the whole (Z, Y, X) extent."""

from __future__ import annotations

import math

from bench.work import io_bytes

# per point and step: the Laplacian (1 multiply for the centre, then for
# each of the 4 radii 5 adds of its 6 neighbours, 1 multiply, 1 add) and
# p_next = 2 c - p_prev + vel2 * lap (2 multiplies, 1 subtract, 1 add)
OPS_PER_POINT_STEP = 1 + 4 * 7 + 4


def work(call):
    shape, _ = call["in"][1]  # p_cur
    steps = int(call["kw"]["steps"])
    return {"bytes": io_bytes(call),
            "ops": OPS_PER_POINT_STEP * steps * math.prod(shape)}
