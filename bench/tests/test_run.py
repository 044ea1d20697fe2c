"""Whole runs at a tiny volume on the CPU, with the look for a chip
skipped: a sound run is correct, and each fault of the timed path that
a cell can have makes ``correct`` false."""

import json
import os
import shutil
import subprocess
import sys

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
