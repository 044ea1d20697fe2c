"""25-point stencil Pallas kernel vs jnp oracle."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.stencil import kernel, ops, ref

import _strict_ieee


def _fields(shape, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    p_prev = jax.random.normal(k1, shape, dtype=jnp.float32)
    p_cur = jax.random.normal(k2, shape, dtype=jnp.float32)
    vel2 = jnp.full(shape, 0.08, dtype=jnp.float32) + 0.02 * ref.ricker_source(
        shape
    )
    return p_prev, p_cur, vel2


# small shapes that exercise every tiling edge: one z/y tile, several,
# extents that do not divide the tile, and x wider than one lane tile
SHAPES = [(8, 8, 8), (4, 8, 16), (16, 16, 16), (12, 20, 32),
          (20, 12, 130), (5, 9, 3)]


@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_matches_ref(shape):
    p_prev, p_cur, vel2 = _fields(shape)
    ppad, cpad = ref.pad_bc(p_prev), ref.pad_bc(p_cur)
    ref_next, ref_lap = ref.wave_step(ppad, cpad, vel2)
    pal_next, pal_lap = kernel.wave_step_pallas(ppad, cpad, vel2)
    np.testing.assert_allclose(
        np.asarray(pal_lap), np.asarray(ref_lap), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(pal_next), np.asarray(ref_next), rtol=1e-6, atol=1e-6
    )


def test_laplacian_of_quadratic_is_exact():
    """lap8 reproduces the analytic Laplacian of a quadratic exactly
    (8th-order scheme is exact on polynomials up to degree 9)."""
    n = 16
    z, y, x = jnp.meshgrid(
        jnp.arange(n, dtype=jnp.float32),
        jnp.arange(n, dtype=jnp.float32),
        jnp.arange(n, dtype=jnp.float32),
        indexing="ij",
    )
    del z, y, x
    # pad with the true polynomial values, not zeros
    h = ref.HALO
    zz, yy, xx = jnp.meshgrid(
        jnp.arange(-h, n + h, dtype=jnp.float32),
        jnp.arange(-h, n + h, dtype=jnp.float32),
        jnp.arange(-h, n + h, dtype=jnp.float32),
        indexing="ij",
    )
    up = 0.5 * zz**2 + 1.5 * yy**2 - 2.0 * xx**2
    lap = ref.laplacian8(up)
    # exact up to f32 cancellation on |u|~4e2 (f64 gives ~1e-12)
    np.testing.assert_allclose(np.asarray(lap), 0.0, atol=1e-3)


def test_temporal_steps_shape_invariance():
    shape = (16, 16, 16)
    p_prev, p_cur, vel2 = _fields(shape)
    pp, pc = ops.temporal_steps(p_prev, p_cur, vel2, steps=3)
    assert pp.shape == shape and pc.shape == shape
    assert bool(jnp.all(jnp.isfinite(pc)))


def test_temporal_steps_match_reference_run():
    """Fixed-shape zero-padded stepping == the in-core reference."""
    shape = (12, 12, 12)
    p_prev, p_cur, vel2 = _fields(shape)
    pp1, pc1 = ops.temporal_steps(p_prev, p_cur, vel2, steps=4)
    pp2, pc2 = ref.run_steps(p_prev, p_cur, vel2, steps=4)
    np.testing.assert_allclose(np.asarray(pc1), np.asarray(pc2), rtol=1e-6)


def test_pallas_temporal_steps():
    shape = (8, 8, 8)
    p_prev, p_cur, vel2 = _fields(shape)
    pp1, pc1 = ops.temporal_steps(p_prev, p_cur, vel2, steps=2, backend="ref")
    pp2, pc2 = ops.temporal_steps(
        p_prev, p_cur, vel2, steps=2, backend="pallas"
    )
    np.testing.assert_allclose(
        np.asarray(pc1), np.asarray(pc2), rtol=1e-5, atol=1e-5
    )


_STRICT_STEP = """
import json, numpy as np, jax.numpy as jnp
from repro.kernels.stencil import kernel, ref
out = {}
for shape in %s:
    rng = np.random.default_rng(sum(shape))
    f = lambda s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    padded = tuple(n + 2 * ref.HALO for n in shape)
    ppad, cpad, v = f(padded), f(padded), 0.05 + 0.01 * f(shape)
    want = ref.wave_step(ppad, cpad, v)
    got = kernel.wave_step_pallas(ppad, cpad, v)
    out[str(shape)] = [bool(np.array_equal(a, b)) for a, b in zip(want, got)]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def strict_step():
    return _strict_ieee.run(_STRICT_STEP % json.dumps(SHAPES))


@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_bit_identical_strict_ieee(strict_step, shape):
    """With every operation rounded (no FMA contraction, see
    ``_strict_ieee``), the single-step kernel is bit-identical to
    ``ref.wave_step`` — p_next and lap — with arbitrary halo values."""
    assert strict_step[str(list(shape))] == [True, True]
