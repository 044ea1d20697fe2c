"""Executor host time per time step outside the host store, in ms: the
union of the engine's ``ooc.visit`` spans less the union of its
``ooc.store.stage``/``ooc.store.put`` spans, in the window. It is the
visits' dispatch: assembly, program calls, slices and bookkeeping."""

from bench.spans import ms_per_step


def read(record):
    return ms_per_step(record, ["ooc.visit"],
                       less=["ooc.store.stage", "ooc.store.put"])
