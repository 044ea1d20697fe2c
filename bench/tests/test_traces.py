"""The per-layer readers on a trace recorded on the chip."""

import copy
import json
from pathlib import Path

import pytest

from bench import spans, traces
from bench.run import load_reader

DATA = json.loads(
    (Path(__file__).parent / "data" / "stream_trace.json").read_text())
METRICS = ["idle_share", "wire_gb_per_step", "store_ms_per_step",
           "stencil_roofline", "encode_roofline", "decode_roofline"]


@pytest.fixture
def record():
    return copy.deepcopy(DATA["record"])


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_the_recorded_run_s_number(metric, record):
    got = load_reader(metric)(record)
    assert got == pytest.approx(DATA["expected"][metric], rel=1e-12)


def test_busy_and_window(record):
    busy_ns, window_ns, _ = traces.busy(record["trace"])
    assert busy_ns / 1e9 == pytest.approx(DATA["expected"]["busy_s"])
    assert window_ns / 1e9 == pytest.approx(DATA["expected"]["window_s"])


def test_shares_never_pass_100(record):
    for metric in ("idle_share", "stencil_roofline", "encode_roofline",
                   "decode_roofline"):
        assert 0 < load_reader(metric)(record) <= 100


def test_breakdown(record):
    ops = traces.top_ops(record["trace"])
    gaps = spans.idle_gaps(record["trace"])
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert ops == sorted(ops, key=lambda o: -o[1])
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # the longest idle stretches of the stream window are host-store puts
    assert gaps[0][0] == "bench.store.put"


def _rename(trace, old, new):
    for plane in trace["device"].values():
        for events in plane.values():
            for e in events:
                if traces.module_name(e[0]) == old:
                    e[0] = new + e[0][len(old):]


@pytest.mark.parametrize("metric,module", [
    ("stencil_roofline", "jit_fused_temporal_steps"),
    ("encode_roofline", "jit_compress"),
    ("decode_roofline", "jit_decompress"),
])
def test_roofline_reads_nothing_when_its_module_is_renamed(
        metric, module, record):
    _rename(record["trace"], module, "jit_renamed_program")
    assert load_reader(metric)(record) is None


def test_roofline_reads_nothing_when_calls_and_executions_disagree(record):
    record["calls"]["compress"].pop()
    assert load_reader("encode_roofline")(record) is None


@pytest.mark.parametrize("metric", METRICS)
def test_reader_reads_nothing_from_an_empty_record(metric):
    assert load_reader(metric)(
        {"trace": {}, "calls": {}, "steps": 0, "transfers": {}}) is None


def test_idle_share_reads_nothing_without_a_module_line(record):
    for plane in record["trace"]["device"].values():
        plane.pop(traces.MODULES_LINE, None)
    assert load_reader("idle_share")(record) is None


def test_union_and_clip():
    assert traces.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert traces.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
