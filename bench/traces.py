"""Device traces for the traced run, and the arithmetic the per-layer
readers share.

``capture`` runs a block under the JAX profiler and returns the trace
reduced to a compact record: every line of every device plane (the
TPU's ``XLA Modules`` and ``XLA Ops`` lines among them) and the host
spans whose names start with ``bench.`` (the harness's) or ``ooc.``
(the engine's own), each event as
``[name, start_ns, duration_ns]`` on the profiler's one clock. The
trace file itself lives in a temporary directory that is removed once
it has been read.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = ("bench.", "ooc.")
WINDOW_SPAN = "bench.window"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

Event = List  # [name, start_ns, duration_ns]


@contextlib.contextmanager
def capture(out: Dict[str, object]):
    """Trace the block; on exit fill ``out`` with ``{"device": {plane:
    {line: [event, ...]}}, "host": [event, ...]}``."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # TraceAnnotation spans
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        out.update(reduce_xspace(
            jax.profiler.ProfileData.from_file(paths[0])))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def reduce_xspace(data) -> Dict[str, object]:
    device: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            device[plane.name] = {
                line.name: [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
                for line in plane.lines
            }
        else:
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    return {"device": device, "host": host}


# ---------------------------------------------------------------------------
# arithmetic on the reduced record
# ---------------------------------------------------------------------------


def window(trace) -> Optional[Tuple[float, float]]:
    """(start_ns, end_ns) of the measured window, from its host span."""
    spans = [e for e in trace.get("host", ()) if e[0] == WINDOW_SPAN]
    if len(spans) != 1:
        return None
    _, start, dur = spans[0]
    return float(start), float(start) + float(dur)


def device_lines(trace, line: str) -> List[List[Event]]:
    """That line of each device plane that has it."""
    return [lines[line] for lines in trace.get("device", {}).values()
            if line in lines]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def busy(trace) -> Optional[Tuple[float, float, List[List[Tuple[float, float]]]]]:
    """(busy_ns averaged over the chips, window_ns, per-chip busy
    intervals): the union of the device's module executions inside
    the window. None where the trace holds no module line or no
    window."""
    win = window(trace)
    lines = device_lines(trace, MODULES_LINE)
    if win is None or not lines:
        return None
    per_chip = [clip(union((s, s + d) for _, s, d in events), *win)
                for events in lines]
    total = sum(sum(b - a for a, b in ivs) for ivs in per_chip)
    return total / len(per_chip), win[1] - win[0], per_chip


def module_name(event_name: str) -> str:
    """``jit_compress(12)`` -> ``jit_compress``."""
    return event_name.split("(", 1)[0].strip()


def module_time(trace, module: str) -> Tuple[int, float]:
    """(executions, device ns) of the jitted program ``module`` on the
    device planes' module lines, inside the window."""
    win = window(trace)
    if win is None:
        return 0, 0.0
    n, ns = 0, 0.0
    for events in device_lines(trace, MODULES_LINE):
        for name, start, dur in events:
            if module_name(name) == module and win[0] <= start <= win[1]:
                n += 1
                ns += dur
    return n, ns


def top_ops(trace, k: int = 10) -> List[list]:
    """The ``k`` device operations that took the most time in the
    window, in seconds summed over their executions, each named by
    the head of its HLO text."""
    win = window(trace)
    if win is None:
        return []
    tot: Dict[str, float] = {}
    for events in device_lines(trace, OPS_LINE):
        for name, start, dur in events:
            if win[0] <= start <= win[1]:
                tot[name] = tot.get(name, 0.0) + dur
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name[:120], ns / 1e9] for name, ns in best]
