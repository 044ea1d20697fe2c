"""The engine's host spans (``repro.core.spans``) under the profiler.

A tiny ``AsyncExecutor`` runs two rounds under ``jax.profiler``, once
streamed (no residency: every unit crosses the link) and once resident
(write-back residency: the flush is the only put). The trace file is
read back with ``ProfileData`` and the spans checked against the
executor's own transfer log: every documented span is there, nested as
the engine calls it, one crossing span per transfer record, and the
jitted programs keep their module names.

A tiny four-shard ``ShardedExecutor`` runs two rounds the same way: one
``ooc.shard`` span per shard a round, one halo span per crossing whose
bytes add up to the coordinator's ``halo_wire`` counter, and the same
bits as one device.
"""

import glob
import os
import pathlib
from collections import Counter

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.core.executor import AsyncExecutor
from repro.core.outofcore import HostUnitStore, OOCConfig, paper_code_fields
from repro.core.sharded import ShardedExecutor
from repro.distributed.fault import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
    RetryPolicy,
)
from repro.kernels.stencil import ref as stencil_ref

SHAPE = (96, 12, 12)
NDIV = 3
ROUNDS = 2
FAR = 1 << 40
MODES = {"streamed": 0, "resident": 1 << 30}
# the coordinator's spans, which only a sharded engine makes
SHARDED = {spans.SHARD, spans.HALO_HELD, spans.HALO_UNIT}
EVERY = {v for k, v in vars(spans).items() if k.isupper()} - SHARDED
NSHARDS = 4  # one 24-plane block a shard: every boundary is a shard's
# what the resident run never does after its warm round: no unit
# crosses host -> device, and no writeback is put before the flush
# and no copy is started ahead of a put
RESIDENT_ABSENT = {spans.STAGE, spans.H2D, spans.D2H_START}


class Span:
    def __init__(self, event):
        self.name = event.name
        self.start = event.start_ns
        self.end = event.start_ns + event.duration_ns
        self.meta = {k: v for k, v in event.stats}

    def inside(self, other) -> bool:
        return other.start <= self.start and self.end <= other.end


def _fields(shape):
    p_cur = np.asarray(stencil_ref.ricker_source(shape), dtype=np.float32)
    return 0.95 * p_cur, p_cur, np.full(shape, 0.07, np.float32)


def _traced_rounds(tmp_path, eng, advance):
    """Run ``ROUNDS`` rounds of ``eng`` under the profiler, then drain
    and flush it; returns the trace file's path."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for _ in range(ROUNDS):
            advance()
        eng.finish()
        eng.flush()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    return path


def _record(tmp_path, cache_bytes):
    cfg = OOCConfig(SHAPE, NDIV, 2, paper_code_fields(4))
    eng = AsyncExecutor(cfg, *_fields(SHAPE),
                        schedule="depth2", cache_bytes=cache_bytes)
    eng.advance_round(FAR)  # compiles, and fills the residency
    eng.finish()
    n0 = len(eng.transfers)
    path = _traced_rounds(tmp_path, eng, lambda: eng.advance_round(FAR))
    events, modules = [], set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ooc."):
                    events.append(Span(e))
                for key, value in e.stats:
                    if key == "hlo_module":
                        modules.add(str(value).split("(", 1)[0])
    return {"spans": events, "modules": modules,
            "transfers": eng.transfers[n0:]}


@pytest.fixture(scope="module", params=sorted(MODES))
def traced(request, tmp_path_factory):
    got = _record(tmp_path_factory.mktemp(request.param),
                  MODES[request.param])
    got["mode"] = request.param
    return got


def _named(traced, name):
    return [s for s in traced["spans"] if s.name == name]


def _within(child, parents):
    return [p for p in parents if child.inside(p)]


def test_every_documented_span_appears(traced):
    want = EVERY - (RESIDENT_ABSENT if traced["mode"] == "resident"
                    else set())
    names = {s.name for s in traced["spans"]}
    assert want <= names, want - names
    assert names <= EVERY, names - EVERY


def test_spans_nest_as_the_engine_calls_them(traced):
    stores = _named(traced, spans.STAGE) + _named(traced, spans.PUT)
    for s in _named(traced, spans.CHECKSUM):
        assert _within(s, stores), "a digest outside every crossing"
    for name, parent in ((spans.WAIT, spans.PUT), (spans.D2H, spans.PUT),
                         (spans.H2D, spans.STAGE),
                         (spans.D2H_START, spans.VISIT),
                         (spans.VISIT, spans.ROUND)):
        parents = _named(traced, parent)
        for s in _named(traced, name):
            assert len(_within(s, parents)) == 1, (name, parent)
    # every crossing happens inside a visit, a finish or a flush
    outer = [s for s in traced["spans"]
             if s.name in (spans.VISIT, spans.FINISH, spans.FLUSH)]
    for s in stores:
        assert _within(s, outer), s.name


def test_each_round_visits_every_block_once(traced):
    rounds = _named(traced, spans.ROUND)
    assert len(rounds) == ROUNDS
    for r in rounds:
        assert r.meta["sweeps"] == 1
        visits = [v for v in _named(traced, spans.VISIT) if v.inside(r)]
        assert [v.meta["block"] for v in visits] == list(range(NDIV))
        assert {v.meta["round"] for v in visits} == {r.meta["round"]}
    assert [r.meta["round"] for r in rounds] == [1, 2]


def test_one_crossing_span_per_transfer_record(traced):
    log = Counter(t.direction for t in traced["transfers"])
    stages, puts = _named(traced, spans.STAGE), _named(traced, spans.PUT)
    assert len(stages) == log["h2d"]
    assert len(puts) == log["d2h"]
    if traced["mode"] == "streamed":
        assert log["h2d"] and log["d2h"]
    for op, got in (("h2d", stages), ("d2h", puts)):
        assert sum(s.meta["bytes"] for s in got) == sum(
            t.wire_bytes for t in traced["transfers"] if t.direction == op)


def test_each_early_put_has_its_copy_started_ahead(traced):
    """Every put that reports ``early`` collects a copy started in an
    earlier visit: one ``ooc.store.d2h.start`` span per such put, of
    the device bytes copied (no fewer than the wire's, which count an
    exponent as 16 bits), the starts leading the puts."""
    puts = [s for s in _named(traced, spans.PUT) if s.meta["early"]]
    starts = _named(traced, spans.D2H_START)
    assert len(starts) == len(puts)
    assert sum(s.meta["bytes"] for s in starts) >= sum(
        p.meta["bytes"] for p in puts)
    if puts:
        assert min(s.start for s in starts) < min(p.start for p in puts)
        assert max(s.end for s in starts) < max(p.start for p in puts)


def test_digests_per_crossing(traced):
    """With no fault injected, a put digests its source bytes once (the
    received copy is the source); a stage digests the stored bytes
    once."""
    digests = _named(traced, spans.CHECKSUM)
    for name in (spans.PUT, spans.STAGE):
        for s in _named(traced, name):
            inner = [d for d in digests if d.inside(s)]
            assert len(inner) == 1, name
            assert inner[0].meta["bytes"] >= s.meta["bytes"]


def test_a_corrupted_put_digests_the_received_copy(tmp_path):
    """Under an injected ``corrupt`` the put digests its source, then
    the corrupted copy (refused), and accepts the retry, which receives
    the source again, without a third digest."""
    cfg = OOCConfig(SHAPE, NDIV, 2, paper_code_fields(1))
    plan = FaultPlan([FaultSpec(kind="corrupt", op="d2h", attempts=1)])
    store = HostUnitStore(cfg, injector=FaultInjector(plan),
                          retry=RetryPolicy(attempts=2))
    value = np.ones((8, 12, 12), np.float32)
    with jax.profiler.trace(str(tmp_path)):
        store.put("p_cur", "R", 0, value)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    got = [Span(e)
           for plane in jax.profiler.ProfileData.from_file(path).planes
           for line in plane.lines for e in line.events
           if e.name.startswith("ooc.")]
    (put,) = [s for s in got if s.name == spans.PUT]
    inner = [d for d in got if d.name == spans.CHECKSUM and d.inside(put)]
    assert [d.meta["bytes"] for d in inner] == [value.nbytes] * 2
    assert store.wire_stats["checksum_failures"] == 1


def test_metadata(traced):
    keys = {
        spans.ROUND: {"round", "sweeps"},
        spans.VISIT: {"round", "block"},
        spans.DRAIN: {"round", "block"},
        spans.DECODE: {"block"},
        spans.STENCIL: {"block"},
        spans.ENCODE: {"block"},
        spans.STAGE: {"field", "unit", "bytes"},
        spans.PUT: {"field", "unit", "bytes", "op", "early"},
        spans.CHECKSUM: {"bytes"}, spans.D2H_START: {"bytes"},
        spans.WAIT: set(), spans.D2H: set(), spans.H2D: set(),
        spans.FINISH: set(), spans.FLUSH: set(),
    }
    assert set(keys) == EVERY
    for s in traced["spans"]:
        assert set(s.meta) == keys[s.name], s.name
    for s in _named(traced, spans.PUT):
        assert s.meta["op"] == "d2h"
        assert s.meta["field"] in ("p_prev", "p_cur")
        # streamed, every put is a drain's, its copy started at park;
        # resident, every put is a flush's, of a value never started
        assert bool(s.meta["early"]) == (traced["mode"] == "streamed")


def test_a_drain_retires_an_earlier_visit(traced):
    """``ooc.drain`` names the visit whose writebacks it retires: one
    that started before the drain."""
    visits = {(v.meta["round"], v.meta["block"]): v
              for v in _named(traced, spans.VISIT)}
    drains = _named(traced, spans.DRAIN)
    assert drains
    for d in drains:
        v = visits.get((d.meta["round"], d.meta["block"]))
        assert v is None or v.start < d.start


def test_the_jitted_programs_keep_their_module_names(traced):
    assert {"jit_fused_temporal_steps", "jit_compress",
            "jit_decompress"} <= traced["modules"]


def test_spans_are_made_by_the_one_helper():
    src = pathlib.Path(spans.__file__).resolve().parents[1]
    users = [p.relative_to(src).as_posix() for p in src.rglob("*.py")
             if "TraceAnnotation" in p.read_text()]
    assert users == ["core/spans.py"]


# ----------------------------------------------------------------------
# the shard coordinator
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    """Two streamed rounds of four shards under the profiler (after a
    warm round), the window's halo counters, and the fields beside
    those of one device after the same number of rounds."""
    shape = (24 * NSHARDS, 12, 12)
    cfg = OOCConfig(shape, NSHARDS, 2, paper_code_fields(4))
    devices = jax.devices()
    eng = ShardedExecutor(
        cfg, *_fields(shape), nshards=NSHARDS, schedule="depth2",
        devices=devices[:NSHARDS] if len(devices) >= NSHARDS else None,
    )
    eng.sweep()
    eng.finish()
    before = eng.transfer_summary()
    path = _traced_rounds(tmp_path_factory.mktemp("sharded"), eng,
                          eng.sweep)
    after = eng.transfer_summary()
    got = [Span(e)
           for plane in jax.profiler.ProfileData.from_file(path).planes
           for line in plane.lines for e in line.events
           if e.name.startswith("ooc.")]
    one = AsyncExecutor(cfg, *_fields(shape), schedule="depth2")
    one.run((1 + ROUNDS) * cfg.bt)
    return {
        "spans": got,
        "halo": {k: after[k] - before[k]
                 for k in ("halo_wire", "halo_count")},
        "rounds": list(range(1, 1 + ROUNDS)),
        "fields": {n: (eng.gather(n), one.gather(n))
                   for n in ("p_prev", "p_cur")},
    }


def test_a_sharded_run_makes_every_span(sharded):
    assert {s.name for s in sharded["spans"]} == EVERY | SHARDED


def test_each_shard_sweeps_once_a_round(sharded):
    shards = sorted(_named(sharded, spans.SHARD), key=lambda s: s.start)
    assert [(s.meta["round"], s.meta["shard"]) for s in shards] == [
        (r, d) for r in sharded["rounds"] for d in range(NSHARDS)]
    for s in shards:
        inner = [r for r in _named(sharded, spans.ROUND) if r.inside(s)]
        assert len(inner) == 1
    assert all(_within(r, shards) for r in _named(sharded, spans.ROUND))


@pytest.mark.parametrize("name,keys,exporters", [
    (spans.SHARD, {"shard", "round"}, range(NSHARDS)),
    (spans.HALO_HELD, {"shard", "field", "bytes"}, range(NSHARDS - 1)),
    (spans.HALO_UNIT, {"shard", "field", "unit", "bytes"},
     range(1, NSHARDS)),
])
def test_coordinator_span_metadata(sharded, name, keys, exporters):
    """A halo span names its exporter; one crossing a boundary, rw
    field and round each way."""
    got = _named(sharded, name)
    assert {frozenset(s.meta) for s in got} == {frozenset(keys)}
    assert {s.meta["shard"] for s in got} == set(exporters)
    if name != spans.SHARD:
        assert len(got) == (NSHARDS - 1) * 2 * ROUNDS
        assert {s.meta["field"] for s in got} == {"p_prev", "p_cur"}


def test_one_halo_span_per_crossing_and_its_bytes(sharded):
    """Every crossing the coordinator counts is one halo span, and the
    spans' bytes add up to the window's ``halo_wire``."""
    halos = _named(sharded, spans.HALO_HELD) + _named(sharded,
                                                      spans.HALO_UNIT)
    assert len(halos) == sharded["halo"]["halo_count"]
    assert sum(s.meta["bytes"] for s in halos) == (
        sharded["halo"]["halo_wire"])


def test_a_unit_halo_holds_the_neighbour_s_put(sharded):
    """Each unit halo span holds the one host-store put that lands it
    in the neighbour's ghost mirror, of the same unit and bytes; a held
    hand-over crosses no store."""
    puts = [s for s in _named(sharded, spans.PUT) if s.meta["op"] == "halo"]
    units = _named(sharded, spans.HALO_UNIT)
    assert len(puts) == len(units)
    for u in units:
        (put,) = [p for p in puts if p.inside(u)]
        assert [put.meta[k] for k in ("field", "unit", "bytes")] == [
            u.meta[k] for k in ("field", "unit", "bytes")]
        assert [c for c in _named(sharded, spans.CHECKSUM) if c.inside(u)]
    for h in _named(sharded, spans.HALO_HELD):
        assert not [p for p in _named(sharded, spans.PUT) if p.inside(h)]


@pytest.mark.parametrize("name", ["p_prev", "p_cur"])
def test_a_traced_sharded_run_is_bit_identical_to_one_device(sharded, name):
    got, want = sharded["fields"][name]
    assert np.array_equal(got, want)
