"""The least work of one call of a device program, from the call's
shapes: one module per program, each with ``work(call)``.

A call is recorded as ``{"in": [[shape, dtype], ...], "out": [[shape,
dtype], ...], "kw": {...}}``: the array leaves of its arguments and of
its result, and its static keyword arguments. The count is the
algorithm's own: every input read once and every output written once,
whatever copies or chunks an implementation adds.
"""

from __future__ import annotations

import math

import numpy as np


def nbytes(leaves) -> int:
    return sum(math.prod(shape) * np.dtype(dtype).itemsize
               for shape, dtype in leaves)


def io_bytes(call) -> int:
    return nbytes(call["in"]) + nbytes(call["out"])
