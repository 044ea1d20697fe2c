"""A kernel's share of its roofline, for the ``*_roofline`` readers.

The least time of the window's calls of a program is their least HBM
bytes (``bench/work``) over the chip's HBM bandwidth (``peaks.json``);
the share is that over the device time of the program's executions in
the trace. No vector peak of the chip is published, so every share is
against the HBM bound; operations per byte are printed beside it.
"""

from __future__ import annotations

import sys
from typing import Optional

from bench.traces import module_time


def share(record, program: str, work) -> Optional[float]:
    """Percent of the HBM roofline that the window's calls of the jitted
    ``program`` reached; None where the trace has no execution of it, or
    not one for each recorded call."""
    trace = record.get("trace")
    calls = record.get("calls", {}).get(program, [])
    if not trace or not calls:
        return None
    n, ns = module_time(trace, f"jit_{program}")
    if n != len(calls) or ns <= 0:
        return None
    counts = [work(c) for c in calls]
    nbytes = sum(c["bytes"] for c in counts)
    ops = [c["ops"] for c in counts]
    least_s = nbytes / record["peaks"]["hbm_bytes_per_s"]
    intensity = (f"{sum(ops) / nbytes!r} ops/byte"
                 if None not in ops else "ops not counted")
    print(f"roofline {program}: {n} calls, {nbytes} bytes, {intensity}, "
          f"device {ns / 1e9!r} s, HBM-bound least {least_s!r} s",
          file=sys.stderr)
    return 100.0 * least_s / (ns / 1e9)
