"""The readers of the engine's own spans (``bench/spans.py`` and the
``*_ms_per_step`` metrics that read ``ooc.*`` spans), on hand-built
records, on traces recorded on the chip, and in whole CPU runs."""

import copy
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import run, spans, traces
from bench.run import load_reader

DATA = Path(__file__).parent / "data"
OLD = json.loads((DATA / "stream_trace.json").read_text())
SPANS = json.loads((DATA / "stream_trace_spans.json").read_text())
NEW = ["checksum_ms_per_step", "d2h_ms_per_step", "h2d_ms_per_step",
       "dispatch_ms_per_step"]
OLD_METRICS = ["idle_share", "wire_gb_per_step", "store_ms_per_step",
               "stencil_roofline", "encode_roofline", "decode_roofline"]
MS = 1e6  # ns


def _ev(name, start_ms, end_ms):
    return [name, start_ms * MS, (end_ms - start_ms) * MS]


def _record():
    """One round of two visits in a 1,000 ms window, 2 steps, spans
    nested as the engine nests them (times in ms)."""
    host = [
        _ev("bench.window", 0, 1000),
        _ev("ooc.round", 10, 990),
        _ev("ooc.visit", 20, 500),
        _ev("bench.store.put", 40, 260),
        _ev("ooc.drain", 40, 260),
        _ev("ooc.store.put", 50, 250),
        _ev("ooc.store.wait", 50, 60),
        _ev("ooc.store.d2h", 60, 100),
        _ev("ooc.store.checksum", 100, 170),
        _ev("ooc.store.checksum", 170, 240),
        _ev("ooc.store.stage", 300, 450),
        _ev("ooc.store.checksum", 310, 400),
        _ev("ooc.store.h2d", 400, 440),
        _ev("ooc.stencil", 450, 495),
        _ev("ooc.visit", 500, 980),
        _ev("ooc.store.stage", 520, 560),
        _ev("ooc.store.checksum", 525, 545),
        _ev("ooc.store.h2d", 545, 560),
        _ev("ooc.stencil", 560, 600),
        _ev("ooc.finish", 985, 1200),
        # a digest that runs past the window counts only inside it
        _ev("ooc.store.checksum", 995, 1100),
    ]
    device = {"/device:TPU:0": {traces.MODULES_LINE: [
        ["jit_decompress(1)", 260 * MS, 40 * MS],
        ["jit_fused_temporal_steps(2)", 455 * MS, 30 * MS],
        ["jit_fused_temporal_steps(2)", 565 * MS, 400 * MS],
    ]}}
    return {"trace": {"host": host, "device": device}, "steps": 2}


# what each reader gives on _record(), in ms a step, worked by hand
WANT = {
    # 70 + 70 + 90 + 20 + 5 (995..1000)
    "checksum_ms_per_step": 255 / 2,
    "d2h_ms_per_step": 40 / 2,
    "h2d_ms_per_step": (40 + 15) / 2,
    # visits 20..980 (960) less put 50..250 and stages 300..450, 520..560
    "dispatch_ms_per_step": (960 - 200 - 150 - 40) / 2,
}


@pytest.mark.parametrize("metric", NEW)
def test_reader_on_nested_spans(metric):
    assert load_reader(metric)(_record()) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", NEW)
def test_a_parent_is_not_summed_with_its_children(metric):
    """Spans of one name that overlap, as a parent and its child would,
    count once: adding a copy inside each changes no reading."""
    rec = _record()
    host = rec["trace"]["host"]
    host += [[n, s + 1, d / 2] for n, s, d in host if n.startswith("ooc.")]
    assert load_reader(metric)(rec) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", NEW)
def test_a_renamed_span_reads_nothing(metric):
    rec = _record()
    rec["trace"]["host"] = [[n.replace("ooc.", "engine."), s, d]
                            for n, s, d in rec["trace"]["host"]]
    assert load_reader(metric)(rec) is None


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_nothing_from_an_empty_record(metric):
    assert load_reader(metric)(
        {"trace": {}, "calls": {}, "steps": 0, "transfers": {}}) is None


def test_dispatch_without_store_spans_is_the_visits():
    rec = _record()
    rec["trace"]["host"] = [e for e in rec["trace"]["host"]
                            if not e[0].startswith("ooc.store.")]
    assert load_reader("dispatch_ms_per_step")(rec) == pytest.approx(480)


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(2, 3), (5, 12)], [(0, 2), (3, 5)]),
    ([(0, 10), (15, 20)], [(-5, 1), (9, 16)], [(1, 9), (16, 20)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 4), (6, 10)], [(4, 6)], [(0, 4), (6, 10)]),
])
def test_minus(a, b, want):
    assert spans.minus(a, b) == want


def test_span_union_clips_to_the_window():
    got = spans.span_union(_record()["trace"], ["ooc.finish"])
    assert got == [(985 * MS, 1000 * MS)]
    assert spans.span_union(_record()["trace"], ["ooc.nothing"]) is None


@pytest.mark.parametrize("lo,hi,want", [
    # inside a digest nested in put < drain < visit < round: the digest,
    # where the largest overlap would name the round
    (110, 160, "ooc.store.checksum"),
    # most of 40..260 lies in the put's children; of them, checksum most
    (40, 260, "ooc.store.checksum"),
    # ties on the start go to the shorter: put 50..250 against wait
    (50, 60, "ooc.store.wait"),
    # the round covers 985..990, then only the window (and finish)
    (980, 1000, "ooc.finish"),
])
def test_innermost(lo, hi, want):
    host = [(n, s / MS, (s + d) / MS) for n, s, d in _record()["trace"]["host"]
            if n != traces.WINDOW_SPAN]
    assert spans.innermost(host, lo, hi) == want


def test_time_outside_every_span_names_the_window():
    assert spans.innermost([("ooc.visit", 0, 1)], 0, 10) == traces.WINDOW_SPAN


def test_idle_gaps_named_by_the_innermost_span():
    gaps = spans.idle_gaps(_record()["trace"])
    assert [g[0] for g in gaps[:3]] == [
        "ooc.store.checksum", "ooc.store.checksum", "ooc.visit"]
    assert gaps == sorted(gaps, key=lambda g: -g[1])


# ---------------------------------------------------------------------------
# traces recorded on the chip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", OLD_METRICS)
def test_the_old_readers_are_bit_identical_on_the_old_trace(metric):
    got = load_reader(metric)(copy.deepcopy(OLD["record"]))
    assert got == OLD["expected"][metric]


@pytest.mark.parametrize("metric", NEW)
def test_the_new_readers_read_nothing_from_a_trace_without_them(metric):
    assert load_reader(metric)(copy.deepcopy(OLD["record"])) is None


# the gaps of the old trace as the largest host-span overlap named them
# before gaps were named by the innermost span
OLD_GAPS = [
    ["bench.store.put", 6.993854175], ["bench.store.put", 5.589175257],
    ["bench.store.put", 5.569577092], ["bench.store.put", 5.344848379],
    ["bench.store.put", 5.319062337], ["bench.store.put", 5.249556038],
    ["bench.store.put", 5.237724221], ["bench.store.stage", 2.05302446],
    ["bench.store.stage", 1.844659628], ["bench.window", 0.011796869],
]


def test_the_old_trace_s_gaps_keep_their_names():
    """A trace with no program spans names its gaps as before."""
    trace = OLD["record"]["trace"]
    assert spans.idle_gaps(trace) == OLD_GAPS
    assert spans.idle_gaps(trace)[0][0] == "bench.store.put"


@pytest.mark.parametrize("metric", OLD_METRICS + NEW)
def test_every_reader_gives_the_recorded_number_on_the_spans_trace(metric):
    got = load_reader(metric)(copy.deepcopy(SPANS["record"]))
    assert got == SPANS["expected"][metric]


def test_the_engine_s_store_spans_match_the_harness_s_wrappers():
    """The union of ``ooc.store.stage``/``put`` is within 2% of
    ``store_ms_per_step`` (the wrappers hold the engine's spans), and
    the four children leave under 1% of it unexplained."""
    rec = SPANS["record"]
    store = spans.ms_per_step(rec, ["ooc.store.stage", "ooc.store.put"])
    wrappers = load_reader("store_ms_per_step")(rec)
    assert 0.98 * wrappers <= store <= wrappers
    kids = spans.ms_per_step(rec, ["ooc.store.checksum", "ooc.store.d2h",
                                   "ooc.store.h2d", "ooc.store.wait"])
    assert 0.99 * store <= kids <= store


def test_the_stream_window_s_longest_gaps_are_digests():
    """Named by the innermost span; the largest overlap named the same
    gaps ``ooc.finish`` and ``ooc.round``, which enclose the digests."""
    gaps = spans.idle_gaps(SPANS["record"]["trace"])
    assert [g[0] for g in gaps[:9]] == ["ooc.store.checksum"] * 9
    assert gaps[0] == ["ooc.store.checksum", 7.118841988]


# ---------------------------------------------------------------------------
# whole CPU runs, with the harness keeping the engine's spans
# ---------------------------------------------------------------------------

SEED = 2**31 + 13


@pytest.mark.parametrize("cell", ["code4.stream-1152", "code1.resident-384"])
def test_a_traced_run_reads_the_engine_s_spans(cell, tiny):
    loaded = tiny(cell)
    stream = cell.startswith("code4.")
    mine = NEW if stream else ["dispatch_ms_per_step"]
    loaded["per_layer"] += [{"name": m, "unit": "ms/step"} for m in mine]
    got = run.run_cell(loaded, SEED, 0.05, True, allow_cpu=True)
    assert got["correct"], got["checks"]
    for m in mine:
        assert got["metrics"][m]["value"] > 0, m


def test_the_cell_as_committed_reports_the_engine_s_spans(tiny):
    """``BENCHMARK.json`` lists the four readers for the stream cell,
    and ``bench/traces.py`` keeps the ``ooc.*`` host events they read:
    a traced run reports each."""
    loaded = tiny("code4.stream-1152")
    assert set(NEW) <= {m["name"] for m in loaded["per_layer"]}
    got = run.run_cell(loaded, SEED, 0.05, True, allow_cpu=True)
    assert got["correct"], got["checks"]
    for m in NEW:
        assert got["metrics"][m]["value"] > 0, m


def test_the_resident_cell_reports_no_dispatch():
    """There the host waits inside ``ooc.stencil``; the reader would
    read the device-paced step, which ``gpts_per_s`` already shows."""
    names = {m["name"] for m in run.load_cell("code1.resident-384")[
        "per_layer"]}
    assert not set(NEW) & names


def _plane(name, line, *events):
    """A profiler plane of one line, as ``ProfileData`` gives it."""
    return SimpleNamespace(name=name, lines=[SimpleNamespace(
        name=line, events=[SimpleNamespace(name=n, start_ns=s, duration_ns=d)
                           for n, s, d in events])])


def test_reduce_xspace_keeps_bench_and_ooc_host_events():
    data = SimpleNamespace(planes=[
        _plane("/host:CPU", "python3", ("bench.window", 0, 100),
               ("ooc.store.d2h", 10, 5), ("jit_compress", 20, 5),
               ("PjitFunction", 30, 1), ("oocx", 40, 1)),
        _plane("/device:TPU:0", "XLA Modules", ("jit_compress(1)", 21, 3)),
    ])
    got = traces.reduce_xspace(data)
    assert got["host"] == [["bench.window", 0, 100],
                           ["ooc.store.d2h", 10, 5]]
    assert got["device"] == {
        "/device:TPU:0": {"XLA Modules": [["jit_compress(1)", 21, 3]]}}
