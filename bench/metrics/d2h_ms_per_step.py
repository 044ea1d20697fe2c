"""Host time materializing writebacks per time step, in ms: the union
of the engine's ``ooc.store.d2h`` spans (``np.asarray`` of a device
value the store has already waited for) in the window."""

from bench.spans import ms_per_step


def read(record):
    return ms_per_step(record, ["ooc.store.d2h"])
