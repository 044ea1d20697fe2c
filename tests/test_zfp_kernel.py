"""Pallas ZFP kernel vs pure-jnp oracle: shape/dtype/rate sweep.

The kernel must be *bit-identical* to the oracle (same fixed-point
construction, same exact power-of-two scaling), not just allclose.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.zfp import kernel, ops, ref

SHAPES = {
    1: [(4,), (64,), (1000,), (4096,)],
    2: [(4, 4), (16, 128), (30, 50), (128, 128)],
    3: [(4, 4, 4), (8, 16, 32), (10, 11, 12), (32, 32, 32)],
}
PLANES = [32, 24, 16, 12, 8, 4, 1]


def _data(shape, seed, scale=7.3):
    key = jax.random.PRNGKey(seed)
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


@pytest.mark.slow
@pytest.mark.parametrize("ndim", [1, 2, 3])
@pytest.mark.parametrize("planes", PLANES)
def test_kernel_bitwise_matches_ref(ndim, planes):
    for i, shape in enumerate(SHAPES[ndim]):
        x = _data(shape, seed=100 * ndim + i)
        cr = ops.compress(x, planes=planes, ndim=ndim, backend="ref")
        cp = ops.compress(x, planes=planes, ndim=ndim, backend="pallas")
        np.testing.assert_array_equal(np.asarray(cr.payload), np.asarray(cp.payload))
        np.testing.assert_array_equal(np.asarray(cr.emax), np.asarray(cp.emax))
        yr = ops.decompress(cr, backend="ref")
        yp = ops.decompress(cp, backend="pallas")
        np.testing.assert_array_equal(np.asarray(yr), np.asarray(yp))
        assert yr.shape == x.shape and yr.dtype == x.dtype


@pytest.mark.parametrize("ndim", [1, 2, 3])
def test_kernel_special_values(ndim):
    """Zero blocks, tiny/denormal values, huge values, mixed signs."""
    n = ref.block_size(ndim)
    rows = np.stack(
        [
            np.zeros(n),
            np.full(n, 1e-40),  # denormal in f32
            np.full(n, 3e38),  # near f32 max
            np.linspace(-1e-3, 1e3, n),
            np.where(np.arange(n) % 2 == 0, 1.0, -1.0) * 0.125,
        ]
    ).astype(np.float32)
    shape = {1: (5 * 4,), 2: (5 * 4, 4), 3: (5 * 4, 4, 4)}[ndim]
    x = jnp.asarray(rows.reshape(shape))
    for planes in (32, 8):
        cr = ops.compress(x, planes=planes, ndim=ndim, backend="ref")
        cp = ops.compress(x, planes=planes, ndim=ndim, backend="pallas")
        np.testing.assert_array_equal(np.asarray(cr.payload), np.asarray(cp.payload))
        np.testing.assert_array_equal(np.asarray(cr.emax), np.asarray(cp.emax))


def test_payload_sizing():
    # fixed-rate: payload size is exactly nb * ceil(payload_bits / 32),
    # stored word-major (W, nb) like every coefficient-major codec array
    x = _data((16, 16, 16), seed=0)
    for planes in PLANES:
        c = ops.compress(x, planes=planes, ndim=3)
        nb = (16 // 4) ** 3
        assert c.payload.shape == (ref.payload_words(3, planes), nb)
        assert c.emax.shape == (nb,)
        assert c.payload.dtype == jnp.uint32
        # exact fixed rate: subband offsets are zero-sum (or disabled)
        assert ref.payload_bits(3, planes) == 64 * min(planes, 32)
        ratio = c.compression_ratio
        assert ratio == pytest.approx(32.0 / ref.bits_per_value(3, planes))


def test_quantize_equals_roundtrip():
    x = _data((32, 32), seed=3)
    for planes in (16, 8):
        q = ops.quantize(x, planes=planes, ndim=2)
        y = ops.decompress(ops.compress(x, planes=planes, ndim=2))
        np.testing.assert_array_equal(np.asarray(q), np.asarray(y))


def test_tile_padding_edge():
    # nb not a multiple of the kernel tile: wrapper pads and strips.
    x = _data((4, 4, 12), seed=4)  # 3 blocks only
    c = ops.compress(x, planes=16, ndim=3, backend="pallas")
    y = ops.decompress(c, backend="pallas")
    yr = ops.decompress(ops.compress(x, planes=16, ndim=3, backend="ref"))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_bucket_tile_bounds_recompilation():
    """Pad-to-tile sizes are power-of-two bucketed (one 128-block lane
    row up to DEFAULT_TILE_BLOCKS) so differently-sized units — e.g. an
    R unit's blocks vs a C unit's — map to a handful of kernel tiles
    instead of one compile per distinct block count."""
    assert ops.bucket_tile(1) == kernel.LANES
    assert ops.bucket_tile(128) == 128
    assert ops.bucket_tile(129) == 256
    assert ops.bucket_tile(600) == 1024
    assert ops.bucket_tile(1024) == kernel.DEFAULT_TILE_BLOCKS
    assert ops.bucket_tile(10_000) == kernel.DEFAULT_TILE_BLOCKS
    # every block count maps to one of four tiles: 128, 256, 512, 1024
    tiles = {ops.bucket_tile(nb) for nb in range(1, 4097)}
    assert tiles == {128, 256, 512, 1024}
    # bucketed padding stays bit-identical to the oracle across bucket
    # boundaries (pad rows are encoded then stripped)
    for planes_z in (4, 36, 80):  # 36, 324, 720 blocks -> tiles differ
        x = _data((planes_z, 24, 24), seed=planes_z)
        cp = ops.compress(x, planes=12, ndim=3, backend="pallas")
        cr = ops.compress(x, planes=12, ndim=3, backend="ref")
        np.testing.assert_array_equal(
            np.asarray(cp.payload), np.asarray(cr.payload)
        )
        np.testing.assert_array_equal(
            np.asarray(ops.decompress(cp, backend="pallas")),
            np.asarray(ops.decompress(cr, backend="ref")),
        )


@pytest.mark.parametrize(
    "shape",
    # 1 block, a 256-block tile, one full 1024-block tile, and a unit
    # over several grid steps of the kernel (1280 blocks)
    [(4, 4, 4), (8, 32, 36), (16, 64, 64), (20, 64, 64)],
)
def test_kernel_tile_layouts_bitwise(shape):
    """Encode and decode through every tile bucket and a multi-step
    grid are bit-identical to the oracle."""
    x = _data(shape, seed=sum(shape))
    cr = ops.compress(x, planes=12, ndim=3, backend="ref")
    cp = ops.compress(x, planes=12, ndim=3, backend="pallas")
    np.testing.assert_array_equal(np.asarray(cr.payload), np.asarray(cp.payload))
    np.testing.assert_array_equal(np.asarray(cr.emax), np.asarray(cp.emax))
    np.testing.assert_array_equal(
        np.asarray(ops.decompress(cp, backend="pallas")),
        np.asarray(ops.decompress(cr, backend="ref")),
    )


def test_decompress_units_batched_matches_single():
    """Batched decode dispatch == per-unit decode, heterogeneous
    shapes (the executor's per-visit burst and gather's reassembly)."""
    xs = [_data((8, 8, 8), seed=1), _data((4, 8, 8), seed=2),
          _data((12, 8, 8), seed=3)]
    cs = ops.compress_units(xs, planes=12, ndim=3)
    batched = ops.decompress_units(cs)
    for c, y in zip(cs, batched):
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(ops.decompress(c))
        )
