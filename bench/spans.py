"""Arithmetic on the engine's own host spans (``ooc.*``, emitted by
``repro.core.spans``) in the reduced trace record, for the readers of
the store, link and executor metrics.

A span's time in the window is the union of its events clipped to
``bench.window``: nested or overlapping events of one name count once.
A reader returns None where its spans are absent from the window, so a
renamed span reads nothing rather than zero.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from bench.traces import WINDOW_SPAN, busy, clip, union, window

Intervals = List[Tuple[float, float]]


def span_union(trace, names: Iterable[str]) -> Optional[Intervals]:
    """The union of the host spans named in ``names`` inside the window;
    None where the window holds none of them."""
    win = window(trace)
    if win is None:
        return None
    names = set(names)
    got = [(s, s + d) for n, s, d in trace.get("host", ()) if n in names]
    return clip(union(got), *win) or None


def minus(a: Intervals, b: Intervals) -> Intervals:
    """The union ``a`` with the union ``b`` taken out (both sorted and
    disjoint, as ``union`` gives them)."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if hi > lo:
            out.append((lo, hi))
    return out


def total_ns(intervals: Intervals) -> float:
    return sum(b - a for a, b in intervals)


def ms_per_step(record, names: Iterable[str],
                less: Iterable[str] = ()) -> Optional[float]:
    """Host ms per time step inside the spans ``names``, less the time
    inside the spans ``less``; None where ``names`` is absent."""
    trace, steps = record.get("trace") or {}, record.get("steps", 0)
    got = span_union(trace, names)
    if got is None or not steps:
        return None
    taken = span_union(trace, less) if less else None
    if taken:
        got = minus(got, taken)
    return total_ns(got) / 1e6 / steps


def innermost(spans, lo: float, hi: float) -> Optional[str]:
    """The name of the span innermost over most of ``[lo, hi]``: at each
    instant the covering span with the latest start counts (ties to the
    shorter); time no span covers counts for ``bench.window``. ``spans``
    is ``[(name, start, end), ...]``."""
    cover = [(n, max(s, lo), min(e, hi), s, e) for n, s, e in spans
             if min(e, hi) > max(s, lo)]
    edges = sorted({lo, hi} | {x for c in cover for x in c[1:3]})
    held = {}
    for a, b in zip(edges, edges[1:]):
        over = [c for c in cover if c[1] <= a and b <= c[2]]
        name = (max(over, key=lambda c: (c[3], c[3] - c[4]))[0]
                if over else WINDOW_SPAN)
        held[name] = held.get(name, 0.0) + (b - a)
    return max(held, key=held.get) if held else None


def idle_gaps(trace, k: int = 10) -> List[list]:
    """The ``k`` longest idle stretches of the first chip inside the
    window, each named by the host span innermost over most of it
    (``bench.window`` stands for host work outside every other span)."""
    got = busy(trace)
    if got is None:
        return []
    lo, hi = window(trace)
    edges = [lo] + [x for iv in got[2][0] for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    spans = [(n, s, s + d) for n, s, d in trace.get("host", ())
             if n != WINDOW_SPAN]
    return [[innermost(spans, a, b), (b - a) / 1e9] for a, b in gaps]
