"""Temporal-k schedule: fused multi-sweep visits across all layers.

The temporal-k contract (graph builder, fused kernel, both engines):

* ``temporal1`` degenerates to ``unitgrain`` — graph task-for-task,
  live engine bit-for-bit and transfer-for-transfer;
* a visit fuses ``k`` sweeps: one fetch (halo-k widened), one fused
  ``bt*k``-step stencil, one writeback carrying ``k`` version bumps —
  steady-state wire bytes per simulated step drop by ~``k``;
* ``k > sweeps_remaining`` truncates on the final round (total steps
  stay exact);
* a halo too wide for the block interior is rejected at config
  validation with an actionable error;
* the fused Pallas kernel is bit-identical to ``k`` sequential
  reference steps in float32 (strict IEEE rounding);
* model and live executor agree transfer-for-transfer at every cache
  budget.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import AsyncExecutor
from repro.core.outofcore import OOCConfig, OutOfCoreWave, paper_code_fields
from repro.core.taskgraph import (
    build_sweep_tasks,
    get_schedule,
    summarize_transfers,
    temporal_k,
)
from repro.kernels.stencil import kernel as stencil_kernel
from repro.kernels.stencil import ops as stencil_ops
from repro.kernels.stencil import ref as stencil_ref

import _strict_ieee

SHAPE = (96, 12, 12)


def _initial(shape=SHAPE):
    p_cur = np.asarray(stencil_ref.ricker_source(shape), dtype=np.float32)
    p_prev = 0.95 * p_cur
    vel2 = np.full(shape, 0.07, dtype=np.float32)
    return p_prev, p_cur, vel2


def _cfg(code=1, ndiv=2, bt=1):
    return OOCConfig(SHAPE, ndiv, bt, paper_code_fields(code))


# ----------------------------------------------------------------------
# schedule parsing + config validation
# ----------------------------------------------------------------------

def test_temporal_schedule_parsing():
    assert get_schedule("temporal4").temporal == 4
    assert get_schedule("temporal-2").temporal == 2
    assert get_schedule("temporal1").temporal == 1
    assert temporal_k(3).name == "temporal3"
    with pytest.raises(ValueError):
        temporal_k(0)
    with pytest.raises(ValueError):
        get_schedule("temporal")


def test_halo_wider_than_block_interior_raises():
    """halo-width > block-interior must fail at OOCConfig validation
    with an error naming the offending geometry, not deep in the
    engine with a shape mismatch."""
    cfg = _cfg(ndiv=4, bt=2)  # block 24; k=4 halo = 4*2*4 = 32
    with pytest.raises(ValueError, match="halo-width .* exceeds the block"):
        cfg.temporal_plan(4)
    with pytest.raises(ValueError, match="halo-width"):
        AsyncExecutor(cfg, *_initial(), schedule="temporal4")
    with pytest.raises(ValueError, match="temporal fusion must be >= 1"):
        cfg.temporal_plan(0)
    # ndiv >= 3 needs strictly more interior (non-empty remainders)
    with pytest.raises(ValueError, match="halo-width"):
        OOCConfig(SHAPE, 3, 2, paper_code_fields(1)).temporal_plan(2)
    # the same k fits a wider block
    assert _cfg(ndiv=2, bt=1).temporal_plan(4).halo == 16


# ----------------------------------------------------------------------
# k=1 degenerates to unitgrain
# ----------------------------------------------------------------------

def test_graph_k1_identical_to_unitgrain():
    cfg = _cfg(code=2, ndiv=4, bt=2)
    a = build_sweep_tasks(cfg, sweeps=3, schedule="temporal1")
    b = build_sweep_tasks(cfg, sweeps=3, schedule="unitgrain")
    assert a == b


@pytest.mark.parametrize("code", [1, 2])
def test_live_k1_bit_identical_to_unitgrain(code):
    cfg = _cfg(code, ndiv=4, bt=2)
    runs = []
    for schedule in ("temporal1", "unitgrain"):
        live = AsyncExecutor(cfg, *_initial(), schedule=schedule)
        live.run(3 * cfg.bt)
        runs.append(live)
    t1, ug = runs
    assert t1.transfers == ug.transfers
    for name in ("p_cur", "p_prev"):
        np.testing.assert_array_equal(t1.gather(name), ug.gather(name))


# ----------------------------------------------------------------------
# truncation + engine agreement
# ----------------------------------------------------------------------

def test_truncated_final_round():
    """6 steps under temporal-4 (bt=1) = one fused round of 4 + a
    truncated round of 2; both engines agree bit-for-bit with each
    other and the versions/steps come out exact."""
    cfg = _cfg(code=1, ndiv=2, bt=1)
    sync = OutOfCoreWave(cfg, *_initial(), temporal=4)
    live = AsyncExecutor(cfg, *_initial(), schedule="temporal4")
    sync.run(6)
    live.run(6)
    assert sync.sweeps_done == live.sweeps_done == 6
    for name in ("p_cur", "p_prev"):
        np.testing.assert_array_equal(live.gather(name), sync.gather(name))
    # in-core agreement (tight tolerance: XLA fuses the full-volume
    # scan differently from the per-round programs)
    pp, pc, v2 = _initial()
    _, gt = stencil_ref.run_steps(
        jnp.asarray(pp), jnp.asarray(pc), jnp.asarray(v2), 6
    )
    np.testing.assert_allclose(
        live.gather("p_cur"), np.asarray(gt), rtol=0, atol=1e-5
    )
    # the graph truncates the same way: rounds of 4 and 2 sweeps, and
    # each writeback bumps by the round's kr (final versions == sweeps)
    tasks = build_sweep_tasks(cfg, sweeps=6, schedule="temporal4")
    d2h_vers = sorted(
        {t.version for t in tasks if t.kind == "d2h" and t.field == "p_cur"}
    )
    assert d2h_vers == [4, 6]


def test_run_rejects_partial_bt():
    cfg = _cfg(code=1, ndiv=2, bt=1)
    live = AsyncExecutor(cfg, *_initial(), schedule="temporal4")
    with pytest.raises(AssertionError):
        live.sweep(5)  # more than the schedule's fusion


# ----------------------------------------------------------------------
# fused kernel numerics
# ----------------------------------------------------------------------

FUSED_STEPS = [1, 2, 3, 4]
# two z-tiles and two y-tiles of the kernel, an x not a lane multiple
FUSED_SHAPE = (32, 32, 8)


def _fused_fields(steps):
    rng = np.random.default_rng(steps)
    pp = jnp.asarray(rng.standard_normal(FUSED_SHAPE).astype(np.float32))
    pc = jnp.asarray(rng.standard_normal(FUSED_SHAPE).astype(np.float32))
    v2 = jnp.asarray(
        (0.05 + 0.01 * rng.standard_normal(FUSED_SHAPE)).astype(np.float32)
    )
    return pp, pc, v2


_STRICT_FUSED = """
import json, numpy as np, jax.numpy as jnp
from repro.kernels.stencil import kernel, ref
out = {}
for steps in %s:
    rng = np.random.default_rng(steps)
    f = lambda: jnp.asarray(rng.standard_normal(%s).astype(np.float32))
    pp, pc = f(), f()
    v2 = 0.05 + 0.01 * f()
    want = ref.ladder_steps(pp, pc, v2, steps)
    got = kernel.wave_multistep_pallas(pp, pc, v2, steps=steps)
    out[str(steps)] = [bool(np.array_equal(a, b)) for a, b in zip(want, got)]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def strict_fused():
    return _strict_ieee.run(
        _STRICT_FUSED % (FUSED_STEPS, FUSED_SHAPE)
    )


@pytest.mark.parametrize("steps", FUSED_STEPS)
def test_fused_kernel_bit_identical_to_sequential_reference(
    strict_fused, steps
):
    """The fused kernel computes ``ref.ladder_steps``' expression tree
    per element: bit-identical to ``steps`` sequential reference steps
    when every operation rounds (no FMA contraction, ``_strict_ieee``),
    across z/y tiles and multi-launch chunks; and within float32
    tightness of the ladder in this process, where XLA may contract."""
    assert strict_fused[str(steps)] == [True, True]
    pp, pc, v2 = _fused_fields(steps)
    fused = stencil_kernel.wave_multistep_pallas(pp, pc, v2, steps=steps)
    ladder = jax.jit(
        stencil_ref.ladder_steps, static_argnames=("steps",)
    )(pp, pc, v2, steps=steps)
    for got, want in zip(fused, ladder):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0, atol=1e-5
        )


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_fused_dispatch_fallback_matches_ladder(backend):
    """``fused_temporal_steps`` (the fused kernel on the Pallas backend)
    equals ``steps`` sequential single-step calls of the same backend."""
    shape = (16, 16, 8)
    rng = np.random.default_rng(7)
    pp = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    pc = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    v2 = jnp.asarray(
        (0.05 + 0.01 * rng.standard_normal(shape)).astype(np.float32)
    )
    a = stencil_ops.fused_temporal_steps(
        pp, pc, v2, steps=2, backend=backend
    )
    b = stencil_ops.temporal_steps(pp, pc, v2, steps=2, backend=backend)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------------------
# wire accounting: model/live parity + the ~k reduction
# ----------------------------------------------------------------------

CACHE_BUDGETS = [0, 100_000, 1 << 30]


@pytest.mark.parametrize("budget", CACHE_BUDGETS)
def test_model_live_transfer_parity_temporal(budget):
    """The temporal graph emits exactly the transfers the live engine
    pays (multiset over kind/field/unit/sweep/flush) at every residency
    budget, and the modeled residency counters match the live ones —
    including the one-deposit/k-bumps accounting."""
    cfg = _cfg(code=2, ndiv=2, bt=2)  # k=2 halo = 16 <= block 48
    live = AsyncExecutor(
        cfg, *_initial(), schedule="temporal2", cache_bytes=budget
    )
    live.run(6 * cfg.bt)  # 3 fused rounds
    pre_gather = live.stats()["cache"]
    stats = {}
    tasks = build_sweep_tasks(
        cfg, sweeps=6, schedule="temporal2", cache_bytes=budget,
        stats=stats,
    )
    graph = sorted(
        (t.kind, t.field, t.unit, t.sweep, t.flush)
        for t in tasks if t.kind in ("h2d", "d2h")
    )
    issued = sorted(
        (t.direction, t.field, t.unit, t.sweep, t.flush)
        for t in live.transfers
    )
    assert issued == graph
    for key in ("hits", "deposits", "version_bumps", "evictions",
                "flushes", "d2h_elided", "dirty_bytes"):
        assert pre_gather[key] == stats[key], key


def test_wire_per_step_drops_by_k():
    """The tentpole's headline: steady-state wire bytes per simulated
    step at k=4 are <= 0.3x the k=1 schedule on the same grid (the
    halo widening costs less than the k-fold revisit it removes)."""
    cfg = _cfg(code=1, ndiv=2, bt=1)
    per_step = {}
    counts = {}
    for k in (1, 4):
        live = AsyncExecutor(cfg, *_initial(), schedule=f"temporal{k}")
        live.run(8)
        s = live.transfer_summary()
        per_step[k] = (s["h2d_wire"] + s["d2h_wire"]) / 8
        counts[k] = (s["h2d_count"], s["d2h_count"])
    assert per_step[4] <= 0.3 * per_step[1]
    # one fetch/writeback per unit per ROUND: counts divide by k
    assert counts[4] == (counts[1][0] // 4, counts[1][1] // 4)
