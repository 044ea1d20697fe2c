"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: JAX describes a v5e topology and the TPU compiler,
which ships with JAX, compiles for it. Interpret-mode tests cannot see
what this catches: block shapes off the (8, 128) tiling, more VMEM than
a kernel may use, operations Mosaic cannot lower. The shapes are those
of ``chip_smoke.py``: 1152 x 1152 planes, a block visit of 160 planes
(144 + 2 x 8 halo), a 128-plane unit for the codec, rate 12.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.stencil import kernel as stencil_kernel
from repro.kernels.zfp import kernel as zfp_kernel
from repro.kernels.zfp import ref as zfp_ref

Z, YX = 160, 1152
UNIT_ROWS = 128 * (YX // 4) ** 2 // 4 // 128  # 128-plane unit: 20736
PLANES = 12


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _cases():
    f32, u32, i32 = jnp.float32, jnp.uint32, jnp.int32
    words = zfp_ref.payload_words(3, PLANES)
    h = 2 * stencil_kernel.HALO
    return {
        "wave_step_pallas": (
            stencil_kernel.wave_step_pallas,
            [((Z + h, YX + h, YX + h), f32)] * 2 + [((Z, YX, YX), f32)],
        ),
        "wave_multistep_pallas": (
            lambda a, b, c: stencil_kernel.wave_multistep_pallas(
                a, b, c, steps=2
            ),
            [((Z, YX, YX), f32)] * 3,
        ),
        "encode_pallas": (
            lambda x: zfp_kernel.encode_pallas(x, planes=PLANES, ndim=3),
            [((64, UNIT_ROWS, 128), f32)],
        ),
        "decode_pallas": (
            lambda p, e: zfp_kernel.decode_pallas(
                p, e, planes=PLANES, ndim=3
            ),
            [((words, UNIT_ROWS, 128), u32), ((UNIT_ROWS, 128), i32)],
        ),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, args = _cases()[name]
    specs = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in args
    ]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
