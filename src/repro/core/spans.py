"""Host spans at the engine's layer boundaries.

Every span is a ``jax.profiler.TraceAnnotation``. With no profiler
running it costs a call (about a microsecond); under
``jax.profiler.trace`` it is an event on the host plane, on the same
clock as the device's ``XLA Modules`` line, with its keyword metadata
as event stats and its name left clean. ``docs/architecture.md``
("Spans") gives the tree.
"""

from __future__ import annotations

import jax

# executor (core/executor.py, AsyncExecutor)
ROUND = "ooc.round"        # sweep: round, sweeps
VISIT = "ooc.visit"        # one block visit of a sweep: round, block
DRAIN = "ooc.drain"        # retiring a visit's writebacks: round, block
DECODE = "ooc.decode"      # the visit's decode dispatch: block
STENCIL = "ooc.stencil"    # assembly, fused stencil call, slices: block
ENCODE = "ooc.encode"      # the visit's encode dispatch: block
FINISH = "ooc.finish"
FLUSH = "ooc.flush"

# host store and link (core/outofcore.py, HostUnitStore)
STAGE = "ooc.store.stage"        # one h2d crossing: field, unit, bytes
PUT = "ooc.store.put"            # one put: field, unit, bytes, op
WAIT = "ooc.store.wait"          # put waiting for the device value
D2H = "ooc.store.d2h"            # put materializing it on the host
H2D = "ooc.store.h2d"            # stage handing the host bytes to JAX
CHECKSUM = "ooc.store.checksum"  # one crc32 digest: bytes

# shard coordinator (core/sharded.py, ShardedExecutor); on a halo
# span ``shard`` is the exporter, whose transfer log records the crossing
SHARD = "ooc.shard"          # one shard's sweep of a round: shard, round
HALO_HELD = "ooc.halo.held"  # held slice, left to right: shard, field, bytes
HALO_UNIT = "ooc.halo.unit"  # unit halo put, right to left: shard, field, unit, bytes


def span(name: str, **meta):
    """The host span ``name``, carrying ``meta`` as event stats."""
    return jax.profiler.TraceAnnotation(name, **meta)
