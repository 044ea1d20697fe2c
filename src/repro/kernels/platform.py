"""Where a Pallas kernel runs compiled and where it is interpreted.

Every kernel wrapper builds its ``pallas_call`` through ``dispatch``:
the choice is made when the program is lowered, from the platform it
is lowered for, so no caller passes an ``interpret`` flag. A TPU gets
the compiled Mosaic kernel; every other platform (the CPU test suite)
gets Pallas interpret mode, which runs the same kernel body as plain
JAX operations.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax


def dispatch(call: Callable, *args):
    """Run ``call(*args, interpret=...)``: ``interpret=False`` when
    lowered for a TPU, ``True`` anywhere else. Only the branch of the
    platform being lowered is compiled."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True),
    )
