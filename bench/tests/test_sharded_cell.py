"""The four-chip cell ``code4.sharded4-1152``: its configuration is the
paper's code 4 laid over four shards, a tiny CPU run of it is correct
and stops being so with the halo left out, and its two halo readers
read what the window holds."""

import json

import pytest

from bench import run
from bench.run import ROOT, load_cell, load_reader

CELL = "code4.sharded4-1152"
SEED = 2**31 + 23
PHYSICS = ("stencil", "dtype", "fields", "bt", "backend", "limits")
MS = 1e6  # ns


def test_the_cell_runs_four_shards_on_four_chips():
    loaded = load_cell(CELL)
    assert loaded["cell"]["chips"] == 4
    assert loaded["job"]["shards"] == 4
    assert loaded["config"]["name"] == "acoustic8-code4-x4"
    assert {m["name"] for m in loaded["per_layer"]} == {
        "halo_gb_per_step", "halo_ms_per_step"}


def test_the_configuration_is_code_4_over_the_cell_s_layout():
    """The physics, codes and limits of ``acoustic8-code4``; the chips,
    shards and volume it states are the cell's and the job's."""
    loaded = load_cell(CELL)
    config, job = loaded["config"], loaded["job"]
    code4 = json.loads((ROOT / "bench/configs/acoustic8-code4.json")
                       .read_text())
    assert {k: config[k] for k in PHYSICS} == {k: code4[k] for k in PHYSICS}
    assert config["chips"] == loaded["cell"]["chips"]
    assert config["shards"] == job["shards"]
    assert config["volume"] == job["shape"]
    assert set(config["reduced"]) <= set(config)
    stream = load_cell("code4.stream-1152")["job"]
    assert {k: v for k, v in job.items() if k not in ("shards", "describes")
            } == {k: v for k, v in stream.items() if k != "describes"}


@pytest.mark.parametrize("trace", [False, True])
def test_a_tiny_run_is_correct(trace, tiny):
    loaded = tiny(CELL, shards=4)
    got = run.run_cell(loaded, SEED, 0.05, trace, allow_cpu=True)
    assert got["correct"], got["checks"]
    assert all(c["value"] == 0.0 for c in got["checks"].values())
    if trace:
        assert set(got["metrics"]) == {"halo_gb_per_step",
                                       "halo_ms_per_step"}
        assert all(m["value"] > 0 for m in got["metrics"].values())


def test_a_tiny_run_without_the_halo_is_not_correct(tiny, monkeypatch):
    from repro.core.executor import AsyncExecutor

    monkeypatch.setattr(AsyncExecutor, "deliver_halo",
                        lambda self, *a, **kw: 0)
    got = run.run_cell(tiny(CELL, shards=4), SEED, 0.05, False,
                       allow_cpu=True)
    assert not got["correct"], got["checks"]


@pytest.mark.parametrize("transfers,steps,want", [
    ({"halo_wire": 3e9, "h2d_wire": 7}, 2, 1.5),
    ({"halo_wire": 1_821_450_240}, 6, 0.30357504),
    ({"halo_wire": 0, "h2d_wire": 7}, 2, None),
    ({"h2d_wire": 7}, 2, None),
    ({"halo_wire": 3e9}, 0, None),
    (None, 2, None),
])
def test_halo_gb_per_step(transfers, steps, want):
    got = load_reader("halo_gb_per_step")(
        {"transfers": transfers, "steps": steps})
    assert got == (None if want is None else pytest.approx(want))


def _ev(name, start_ms, end_ms):
    return [name, start_ms * MS, (end_ms - start_ms) * MS]


def _record(host, steps=2):
    return {"trace": {"host": [_ev("bench.window", 0, 1000)] + host,
                      "device": {}}, "steps": steps}


def test_halo_ms_per_step_is_the_union_of_the_halo_spans():
    """Held hand-overs and unit puts in the window, each counted once
    where a store span nests inside; a span past the window's end
    counts up to it; other spans count nothing."""
    record = _record([
        _ev("ooc.shard", 0, 400),
        _ev("ooc.halo.held", 400, 401),
        _ev("ooc.shard", 401, 800),
        _ev("ooc.halo.unit", 800, 900),
        _ev("ooc.store.put", 800, 899),
        _ev("ooc.store.checksum", 850, 899),
        _ev("ooc.halo.unit", 990, 1100),
    ])
    got = load_reader("halo_ms_per_step")(record)
    assert got == pytest.approx((1 + 100 + 10) / 2)


@pytest.mark.parametrize("host,steps", [
    ([_ev("ooc.shard", 0, 400), _ev("ooc.store.put", 400, 500)], 2),
    ([_ev("ooc.halo.unit", 1100, 1200)], 2),
    ([_ev("ooc.halo.unit", 100, 200)], 0),
])
def test_halo_ms_per_step_reads_nothing_without_halo_spans(host, steps):
    assert load_reader("halo_ms_per_step")(_record(host, steps)) is None
