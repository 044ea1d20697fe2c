"""Out-of-core engine vs in-core reference: the paper's core invariant.

* With no compression the out-of-core sweep must reproduce the in-core
  run exactly (same op order on same values).
* With fixed-rate compression the error must stay within the codec's
  analytic ballpark and decay with rate, mirroring paper Fig. 7.
* Transfer accounting must show the separate-compression savings
  (common regions fetched once) and the compression savings on the wire.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blocks import BlockPlan
from repro.core.outofcore import (
    FieldSpec,
    OOCConfig,
    OutOfCoreWave,
    paper_code_fields,
)
from repro.kernels.stencil import ref as stencil_ref

SHAPE = (96, 16, 16)
NDIV, BT = 4, 2


def _initial(shape):
    p_cur = np.asarray(stencil_ref.ricker_source(shape), dtype=np.float32)
    p_prev = 0.95 * p_cur
    vel2 = np.full(shape, 0.07, dtype=np.float32)
    return p_prev, p_cur, vel2


def _incore(p_prev, p_cur, vel2, steps):
    pp, pc = stencil_ref.run_steps(
        jnp.asarray(p_prev), jnp.asarray(p_cur), jnp.asarray(vel2), steps
    )
    return np.asarray(pp), np.asarray(pc)


def test_blockplan_cover_and_sizes():
    plan = BlockPlan(1152, 8, 12)
    plan.check_cover()
    assert plan.halo == 48
    # paper: interior blocks save 2H planes of H2D via sharing
    assert plan.h2d_planes(3, shared=False) - plan.h2d_planes(3) == 96


@pytest.mark.parametrize("sweeps", [1, 3])
def test_uncompressed_matches_incore(sweeps):
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, NDIV, BT, paper_code_fields(1))
    eng = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    eng.run(sweeps * BT)
    ref_pp, ref_pc = _incore(p_prev, p_cur, vel2, sweeps * BT)
    np.testing.assert_allclose(eng.gather("p_cur"), ref_pc, rtol=0, atol=0)
    np.testing.assert_allclose(eng.gather("p_prev"), ref_pp, rtol=0, atol=0)


@pytest.mark.slow
@pytest.mark.parametrize("code,max_rel", [(2, 5e-3), (3, 1e-4), (4, 5e-2)])
def test_compressed_error_bounded(code, max_rel):
    """Paper codes 2-4: lossy but bounded; error grows mildly with steps."""
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, NDIV, BT, paper_code_fields(code))
    eng = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    steps = 3 * BT
    eng.run(steps)
    _, ref_pc = _incore(p_prev, p_cur, vel2, steps)
    got = eng.gather("p_cur")
    scale = np.abs(ref_pc).max()
    rel = np.abs(got - ref_pc).max() / scale
    assert rel < max_rel, (code, rel)


@pytest.mark.slow
def test_error_decreases_with_rate():
    p_prev, p_cur, vel2 = _initial(SHAPE)
    steps = 2 * BT
    _, ref_pc = _incore(p_prev, p_cur, vel2, steps)
    errs = []
    for planes in (8, 12, 16, 24):
        fields = {
            "p_prev": FieldSpec("rw", planes),
            "p_cur": FieldSpec("rw", planes),
            "vel2": FieldSpec("ro", planes),
        }
        eng = OutOfCoreWave(
            OOCConfig(SHAPE, NDIV, BT, fields), p_prev, p_cur, vel2
        )
        eng.run(steps)
        errs.append(np.abs(eng.gather("p_cur") - ref_pc).max())
    assert errs[0] > errs[-1]
    assert all(e >= 0 for e in errs)


def test_transfer_accounting():
    p_prev, p_cur, vel2 = _initial(SHAPE)
    plan = BlockPlan(SHAPE[0], NDIV, BT)
    # code 2: p_prev compressed at 16/32 -> h2d wire for p_prev roughly
    # half of raw (plus emax headers)
    cfg = OOCConfig(SHAPE, NDIV, BT, paper_code_fields(2))
    eng = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    eng.sweep()
    tp = [t for t in eng.transfers if t.field == "p_prev" and
          t.direction == "h2d"]
    raw = sum(t.raw_bytes for t in tp)
    wire = sum(t.wire_bytes for t in tp)
    assert 0.45 < wire / raw < 0.55, wire / raw
    # sharing: each field fetches each common region exactly once/sweep
    tc = [t for t in eng.transfers if t.unit[0] == "C" and
          t.direction == "h2d" and t.field == "p_cur"]
    assert len(tc) == NDIV - 1
    # with sharing every unit crosses the link exactly once per sweep:
    planes = sum(plan.h2d_planes(i) for i in range(NDIV))
    assert planes == SHAPE[0]
    # without sharing each internal common region is fetched twice:
    noshare = sum(plan.h2d_planes(i, shared=False) for i in range(NDIV))
    assert noshare == SHAPE[0] + (NDIV - 1) * 2 * plan.halo


def test_writeback_units_once_per_sweep():
    p_prev, p_cur, vel2 = _initial(SHAPE)
    cfg = OOCConfig(SHAPE, NDIV, BT, paper_code_fields(1))
    eng = OutOfCoreWave(cfg, p_prev, p_cur, vel2)
    eng.sweep()
    d2h = [t for t in eng.transfers if t.direction == "d2h" and
           t.field == "p_cur"]
    units = [t.unit for t in d2h]
    assert len(units) == len(set(units)) == 2 * NDIV - 1  # R_i + C_i
    # read-only field is never written back
    assert not [t for t in eng.transfers if t.direction == "d2h" and
                t.field == "vel2"]


@pytest.mark.parametrize("code", [1, 4])
def test_pallas_backend_engine_matches_ref_backend(code):
    """The live engine on the Pallas kernels (fused stencil, codec)
    matches the XLA backend and the in-core reference: within float32
    tightness with no compression (the two programs may contract
    multiply-adds differently), within the codec's error at code 4."""
    from repro.core.executor import AsyncExecutor

    shape = (48, 16, 16)
    p_prev, p_cur, vel2 = _initial(shape)
    out = {}
    for backend in ("ref", "pallas"):
        cfg = OOCConfig(shape, 2, BT, paper_code_fields(code),
                        backend=backend)
        eng = AsyncExecutor(cfg, p_prev, p_cur, vel2, schedule="depth2")
        eng.run(2 * BT)
        out[backend] = eng.gather("p_cur")
    _, ref_pc = _incore(p_prev, p_cur, vel2, 2 * BT)
    scale = np.abs(ref_pc).max()
    tol = 1e-5 if code == 1 else 0.1
    for got in out.values():
        assert np.abs(got - ref_pc).max() <= tol * scale
    assert np.abs(out["pallas"] - out["ref"]).max() <= tol * scale
