"""Host time inside the host store's ``stage`` and ``put`` per time
step, in ms: the union of the spans that the traced run wraps around
those two methods (``bench.store.*``)."""

from bench.traces import clip, union, window


def read(record):
    trace, steps = record.get("trace") or {}, record.get("steps", 0)
    win = window(trace)
    spans = [(s, s + d) for n, s, d in trace.get("host", ())
             if n.startswith("bench.store.")]
    if win is None or not spans or not steps:
        return None
    ns = sum(b - a for a, b in clip(union(spans), *win))
    return ns / 1e6 / steps
