"""Share of the window in which no program ran on the device: 1 minus
the union of the device's module executions over the window (device
trace), averaged over the chips."""

from bench.traces import busy


def read(record):
    got = busy(record.get("trace") or {})
    if got is None or got[1] <= 0:
        return None
    busy_ns, window_ns, _ = got
    return 100.0 * (1.0 - busy_ns / window_ns)
