"""Host time handing staged units to JAX per time step, in ms: the
union of the engine's ``ooc.store.h2d`` spans (``jnp.asarray`` from
host memory) in the window. Link time left in flight after the call
returns is not in it."""

from bench.spans import ms_per_step


def read(record):
    return ms_per_step(record, ["ooc.store.h2d"])
