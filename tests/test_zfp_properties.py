"""Property-based tests (hypothesis) for the codec's invariants."""

import pytest

pytest.importorskip("hypothesis")

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis.extra import numpy as hnp

from repro.kernels.zfp import ops, ref

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=25, derandomize=True
)
hypothesis.settings.load_profile("ci")


def _finite_arrays(ndim):
    shape = {1: (16,), 2: (8, 8), 3: (8, 8, 8)}[ndim]
    return hnp.arrays(
        np.float32,
        shape,
        elements=st.floats(
            min_value=np.float32(-1e30),
            max_value=np.float32(1e30),
            allow_nan=False,
            allow_infinity=False,
            width=32,
        ),
    )


@given(x=_finite_arrays(3), planes=st.sampled_from([32, 24, 16, 8, 4]))
def test_error_bound_holds(x, planes):
    """|decode(encode(x)) - x| <= analytic per-block bound."""
    xj = jnp.asarray(x)
    xb = ref.blockify(xj, 3)
    emax = ref.block_emax(xb)
    y = ref.quantize_blocks(xb, planes, 3)
    bound = ref.max_abs_error_bound(emax, planes, 3, jnp.float32)
    err = jnp.max(jnp.abs(y - xb), axis=0)
    assert bool(jnp.all(err <= bound + 1e-37)), (
        float(jnp.max(err - bound)),
        planes,
    )


@given(x=_finite_arrays(2), planes=st.sampled_from([32, 16, 8]))
def test_pack_unpack_inverse(x, planes):
    xb = ref.blockify(jnp.asarray(x), 2)
    emax = ref.block_emax(xb)
    q = ref.to_fixedpoint(xb, emax)
    u = ref.truncate_planes(
        ref.to_negabinary(ref.fwd_transform(q, 2)), planes, 2
    )
    u2 = ref.unpack_planes(ref.pack_planes(u, planes, 2), planes, 2, jnp.float32)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(u2))


@given(x=_finite_arrays(1))
def test_lift_exactly_invertible(x):
    xb = ref.blockify(jnp.asarray(x), 1)
    emax = ref.block_emax(xb)
    q = ref.to_fixedpoint(xb, emax)
    for ndim, qq in ((1, q),):
        c = ref.fwd_transform(qq, ndim)
        q2 = ref.inv_transform(c, ndim)
        np.testing.assert_array_equal(np.asarray(qq), np.asarray(q2))


@given(x=_finite_arrays(3))
def test_lift3d_exactly_invertible(x):
    xb = ref.blockify(jnp.asarray(x), 3)
    q = ref.to_fixedpoint(xb, ref.block_emax(xb))
    c = ref.fwd_transform(q, 3)
    np.testing.assert_array_equal(
        np.asarray(q), np.asarray(ref.inv_transform(c, 3))
    )


@given(x=_finite_arrays(3))
def test_negabinary_roundtrip(x):
    xb = ref.blockify(jnp.asarray(x), 3)
    q = ref.to_fixedpoint(xb, ref.block_emax(xb))
    c = ref.fwd_transform(q, 3)
    np.testing.assert_array_equal(
        np.asarray(c), np.asarray(ref.from_negabinary(ref.to_negabinary(c)))
    )


@given(
    x=hnp.arrays(
        np.float32,
        (8, 8, 8),
        elements=st.floats(
            min_value=-100, max_value=100, allow_nan=False, width=32
        ),
    )
)
def test_error_nonincreasing_in_planes_smooth(x):
    """On smoothed data, more planes never hurt (monotone rate-distortion)."""
    # smooth the random field so decorrelation behaves like stencil data
    xs = jnp.asarray(x)
    k = jnp.ones((3, 3, 3)) / 27.0
    xs = jax.scipy.signal.convolve(xs, k, mode="same")
    errs = []
    for planes in (4, 8, 16, 32):
        y = ops.quantize(xs, planes=planes, ndim=3)
        errs.append(float(jnp.max(jnp.abs(y - xs))))
    assert errs[0] >= errs[1] >= errs[2] >= errs[3]


def test_f64_paper_rates():
    """Paper-faithful f64 path: rates 32/64 and 24/64 hit the paper's
    error ballpark (1e-6..1e-7 relative) on smooth wave-like data."""
    from jax import config as jcfg

    jcfg.update("jax_enable_x64", True)
    try:
        z = np.linspace(0, 4 * np.pi, 64)
        x, y, zz = np.meshgrid(z, z, z, indexing="ij")
        wave = (np.sin(x) * np.cos(0.7 * y) * np.sin(1.3 * zz)).astype(
            np.float64
        )
        xj = jnp.asarray(wave, dtype=jnp.float64)
        assert xj.dtype == jnp.float64
        for planes, lo, hi in ((32, 0.0, 5e-7), (24, 0.0, 2e-4)):
            q = ref.quantize(xj, planes, 3)
            rel = float(
                jnp.max(jnp.abs(q - xj)) / jnp.max(jnp.abs(xj))
            )
            assert lo <= rel <= hi, (planes, rel)
    finally:
        jcfg.update("jax_enable_x64", False)
