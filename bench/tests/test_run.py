"""Whole runs at a tiny volume on the CPU, with the look for a chip
skipped: a sound run is correct, and each fault of the timed path that
a cell can have makes ``correct`` false."""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.run import ROOT

SEED = 2**31 + 11


def test_a_run_on_the_cpu_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "code1.resident-384",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_a_checkout_without_the_engine_is_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "code4.stream-1152",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("cell", ["code4.stream-1152", "code1.resident-384"])
@pytest.mark.parametrize("trace", [False, True])
def test_a_sound_run_is_correct(cell, trace, tiny):
    got = run.run_cell(tiny(cell), SEED, 0.05, trace, allow_cpu=True)
    assert got["correct"], got["checks"]
    assert list(got)[-1] == "checks"
    assert got["attempted"] >= 1 and got["failed"] == 0
    json.dumps(got)
    if trace:
        assert set(got["metrics"]) <= {
            m["name"] for m in tiny(cell)["per_layer"]}
    else:
        assert set(got["metrics"]) == {"gpts_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in got["metrics"].values())


@pytest.mark.parametrize("trace", [False, True])
def test_a_sound_sharded_run_is_correct(trace, tiny):
    loaded = tiny("code4.stream-1152", shards=4)
    got = run.run_cell(loaded, SEED, 0.05, trace, allow_cpu=True)
    assert got["correct"], got["checks"]
    assert list(got)[-1] == "checks"
    assert got["attempted"] >= 1 and got["failed"] == 0
    json.dumps(got)
    if trace:
        assert set(got["breakdown"]) == {"device_ops", "idle_gaps"}
        assert got["metrics"] and set(got["metrics"]) <= {
            m["name"] for m in loaded["per_layer"]}
    else:
        assert set(got["metrics"]) == {"gpts_per_s", "setup_s"}


def test_a_sharded_engine_s_units_are_read_from_their_owners(tiny):
    """Every unit is read once, from the shard that owns it (never from
    a neighbour's ghost mirror), and the region read is the engine's
    own gather of the same points."""
    import numpy as np

    from bench import reference

    loaded = tiny("code4.stream-1152", shards=4)
    job = loaded["job"]
    p, v = (np.asarray(a) for a in reference.fields(job["shape"], SEED))
    eng, advance = run.build_engine(
        run.engine_config(loaded["config"], job), job, p, v, None)
    advance()
    got = run.owners(eng)
    assert [store for store, _, _ in got] == [ex.store for ex in eng.shards]
    for (_, _, units), spec in zip(got, eng.specs):
        assert {u[:2] for u in units} == set(spec.owned_units())
        assert not {u[:2] for u in units} & set(spec.ghost_units())
    assert sorted(u for _, _, units in got for u in units) == sorted(
        eng.plan.units())
    region = run.check_region(job, SEED)
    (z0, z1), (y0, y1), (x0, x1) = region
    read = run.read_region(eng, region)
    for name in ("p_prev", "p_cur"):
        np.testing.assert_array_equal(
            read[name], eng.gather(name)[z0:z1, y0:y1, x0:x1])


def _state_unchanged(orig):
    def f(p_prev, p_cur, vel2, **kw):
        return p_prev, p_cur
    return f


def _answer_altered(orig):
    def f(*args, **kw):
        pp, pc = orig(*args, **kw)
        return pp, pc + 0.05 * jnp.max(jnp.abs(pc))
    return f


def _half_left_out(orig):
    calls = []

    def f(p_prev, p_cur, vel2, **kw):
        calls.append(None)
        if len(calls) % 2:
            return p_prev, p_cur
        return orig(p_prev, p_cur, vel2, **kw)
    return f


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered,
                                   _half_left_out])
@pytest.mark.parametrize("cell", ["code4.stream-1152", "code1.resident-384"])
def test_a_broken_stencil_is_not_correct(fault, cell, tiny, monkeypatch):
    from repro.kernels.stencil import ops

    monkeypatch.setattr(ops, "fused_temporal_steps",
                        fault(ops.fused_temporal_steps))
    got = run.run_cell(tiny(cell), SEED, 0.05, False, allow_cpu=True)
    assert not got["correct"], got["checks"]


def test_a_codec_at_a_lower_rate_is_not_correct(tiny, monkeypatch):
    from repro.kernels.zfp import ops

    orig = ops.compress

    def compress(x, *, planes, **kw):
        return orig(x, planes=planes - 4, **kw)

    monkeypatch.setattr(ops, "compress", compress)
    got = run.run_cell(tiny("code4.stream-1152"), SEED, 0.05, False,
                       allow_cpu=True)
    assert not got["correct"], got["checks"]


def test_a_missing_halo_is_not_correct(tiny, monkeypatch):
    """The exchange between shards left out: each shard's ghost mirror
    keeps its neighbour's boundary unit from seeding."""
    from repro.core.executor import AsyncExecutor

    monkeypatch.setattr(AsyncExecutor, "deliver_halo",
                        lambda self, *a, **kw: 0)
    got = run.run_cell(tiny("code4.stream-1152", shards=4), SEED, 0.05,
                       False, allow_cpu=True)
    assert not got["correct"], got["checks"]


class _Chip:
    platform, device_kind = "tpu", "TPU v5 lite"


@pytest.mark.parametrize("shards,chips", [(4, 1), (1, 4), (2, 4)])
def test_a_job_whose_shards_are_not_the_cell_s_chips_is_refused(
        shards, chips, tiny, monkeypatch):
    """On the chip path, before any set-up; with the look for a chip
    skipped the shards share the one device."""
    loaded = tiny("code4.stream-1152")
    loaded["job"]["shards"] = shards
    loaded["cell"] = dict(loaded["cell"], chips=chips)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()] * 4)
    with pytest.raises(run.Refused, match="shard"):
        run.run_cell(loaded, SEED, 0.05, False)
    job, cell = loaded["job"], loaded["cell"]
    assert run.shard_devices(job, cell, [_Chip()] * 4, True) is None


def test_the_shards_are_pinned_one_to_each_of_the_cell_s_chips():
    chips = [object() for _ in range(8)]
    cell = {"chips": 4}
    assert run.shard_devices({"shards": 4}, cell, chips, False) == chips[:4]
    assert run.shard_devices({}, {"chips": 1}, chips, False) is None
