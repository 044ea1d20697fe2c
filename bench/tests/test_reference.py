"""The benchmark's own reference and its control."""

import numpy as np
import pytest

from bench import control, reference


@pytest.mark.parametrize("planes", [2, 8, 12, 16, 28, 32])
@pytest.mark.parametrize("scale", [1.0, 1e-3, 1e5])
def test_quantize_is_the_codec_s_round_trip(planes, scale):
    """The plain block codec gives the engine codec's values bit for
    bit (the engine is imported here, by the test, never by the
    reference)."""
    import jax.numpy as jnp

    from repro.kernels.zfp import ops as zfp_ops

    rng = np.random.default_rng(planes)
    x = rng.standard_normal((8, 12, 16)).astype(np.float32) * scale
    x[:4, :4, :4] = 0.0  # a block of zeros
    got = np.asarray(reference.quantize(jnp.asarray(x), planes=planes))
    want = np.asarray(zfp_ops.quantize(jnp.asarray(x), planes=planes, ndim=3))
    assert np.array_equal(got, want)


def test_stencil_is_the_engine_s_oracle():
    from repro.kernels.stencil import ref

    shape = (40, 24, 32)
    p, v = reference.fields(shape, 2**31 + 3)
    out = reference.run_reference(shape, 2**31 + 3, tuple((0, n) for n in shape),
                                  3, 2, {"p_prev": None, "p_cur": None,
                                         "vel2": None})
    _, want = ref.run_steps(p, p, v, 6)
    assert np.array_equal(out["p_cur"], np.asarray(want))


@pytest.mark.parametrize("planes", [None, 12])
def test_a_region_s_cone_gives_the_whole_volume_s_values(planes):
    shape = (96, 64, 64)
    spec = {"p_prev": planes, "p_cur": None, "vel2": planes}
    whole = reference.run_reference(shape, 5, tuple((0, n) for n in shape),
                                    2, 2, spec)
    region = ((40, 56), (24, 40), (20, 36))
    assert reference.cone(shape, region, 4, grain=4) == (
        (24, 72), (8, 56), (4, 52))
    assert reference.cone(shape, region, 4, grain=32) == (
        (24, 88), (0, 64), (0, 64))
    crop = tuple(slice(lo, hi) for lo, hi in region)
    for grain in (4, 32):
        part = reference.run_reference(shape, 5, region, 2, 2, spec,
                                       grain=grain)
        for name in ("p_prev", "p_cur"):
            assert np.array_equal(part[name], whole[name][crop])


def test_a_box_is_the_same_points_of_the_whole_volume():
    shape = (48, 40, 36)
    p, v = (np.asarray(a) for a in reference.fields(shape, 9))
    box = ((8, 20), (4, 40), (12, 24))
    q, w = (np.asarray(a) for a in reference.fields(shape, 9, box))
    crop = tuple(slice(lo, hi) for lo, hi in box)
    assert np.array_equal(q, p[crop])
    # XLA:CPU's vectorized sine can differ in the last bit with an
    # element's position in the array; on the TPU a box and the whole
    # volume agree bit for bit (the runs' checks read 0)
    np.testing.assert_allclose(w, v[crop], rtol=2e-6, atol=0)


def test_seeds_change_the_velocity_field():
    a = np.asarray(reference.fields((16, 16, 16), 1)[1])
    b = np.asarray(reference.fields((16, 16, 16), 2**31 + 1)[1])
    assert not np.array_equal(a, b)


@pytest.mark.parametrize("cell,rounds", [("code4.stream-1152", 2),
                                         ("code1.resident-384", 8)])
def test_the_control_fails_the_cell_s_limits(cell, rounds, tiny):
    loaded = tiny(cell)
    limits = loaded["config"]["limits"]
    for seed in (1, 2, 3):
        got = control.readings(loaded, seed, rounds)
        assert any(v > limits[k[4:]] for k, v in got.items()), got
