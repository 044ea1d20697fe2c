"""Pallas TPU kernels for the fixed-rate ZFP-style codec.

TPU adaptation notes (vs cuZFP's CUDA implementation):

* cuZFP assigns one warp per 4^d block and uses warp shuffles /
  ``__ballot_sync`` for the bit-plane transpose. TPUs have no warp
  semantics. The codec is coefficient-major instead (``ref.blockify``):
  a kernel tile is ``(4^d, TR, 128)``, row ``i`` holding coefficient
  ``i`` of 128*TR blocks, one block per (sublane, lane) slot. Every
  codec stage is then a whole-vreg operation on coefficient slabs:
  exponent extraction, fixed-point conversion, the lifting transform
  (which combines statically known slabs), negabinary, and the bit-
  plane transpose, a static shift-and-or of slabs.
* The level-order permutation and the bit positions depend only on
  ``(planes, ndim)``: static slices, no gather and no table inputs.
* So the kernel bodies ARE ``ref.encode_blocks`` / ``decode_blocks``,
  run on a VMEM tile: bit-identical to the oracle by construction, and
  every stage is integer arithmetic or an exact power-of-two scaling.
* cuZFP's per-bit-plane group testing (the sequential part the paper
  § IV complains about in cuSZ) is dropped: in fixed-rate mode,
  truncation at a fixed plane is equivalent and branch-free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import dispatch

from . import ref

LANES = 128
# Blocks per grid step at most: TR = 8 sublane rows of 128 lanes, one
# vreg per coefficient slab. VMEM per step: 64 slabs in + <= 64 words
# out, 256 KiB each way.
DEFAULT_TILE_BLOCKS = 8 * LANES


def _encode_kernel(x_ref, payload_ref, emax_ref, *, planes: int,
                   ndim: int):
    payload_ref[...], emax_ref[...] = ref.encode_blocks(
        x_ref[...], planes, ndim
    )


def _decode_kernel(payload_ref, emax_ref, x_ref, *, planes: int,
                   ndim: int):
    x_ref[...] = ref.decode_blocks(
        payload_ref[...], emax_ref[...], planes, ndim, jnp.float32
    )


def _tile_rows(rows: int) -> int:
    return min(rows, DEFAULT_TILE_BLOCKS // LANES)


def _encode_call(xs, *, planes: int, ndim: int, interpret: bool):
    n, rows, _ = xs.shape
    tr = _tile_rows(rows)
    nwords = ref.payload_words(ndim, planes)
    return pl.pallas_call(
        functools.partial(_encode_kernel, planes=planes, ndim=ndim),
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((n, tr, LANES), lambda i: (0, i, 0))],
        out_specs=[
            pl.BlockSpec((nwords, tr, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nwords, rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(xs)


def _decode_call(payload, emax, *, planes: int, ndim: int,
                 interpret: bool):
    nwords, rows, _ = payload.shape
    tr = _tile_rows(rows)
    n = ref.block_size(ndim)
    return pl.pallas_call(
        functools.partial(_decode_kernel, planes=planes, ndim=ndim),
        grid=(rows // tr,),
        in_specs=[
            pl.BlockSpec((nwords, tr, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n, tr, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(payload, emax)


@functools.partial(jax.jit, static_argnames=("planes", "ndim"))
def encode_pallas(xs: jax.Array, *, planes: int, ndim: int):
    """xs: coefficient-major (4^ndim, rows, 128) f32; block b is at
    ``[:, b // 128, b % 128]``. ``rows`` is at most 8 or a multiple of
    8. Returns (payload (W, rows, 128) uint32, emax (rows, 128) int32),
    the same layout."""
    n, rows, lanes = xs.shape
    assert n == ref.block_size(ndim) and lanes == LANES, xs.shape
    assert rows % _tile_rows(rows) == 0, rows
    call = functools.partial(_encode_call, planes=planes, ndim=ndim)
    return tuple(dispatch(call, xs))


@functools.partial(jax.jit, static_argnames=("planes", "ndim"))
def decode_pallas(payload: jax.Array, emax: jax.Array, *, planes: int,
                  ndim: int):
    """Inverse of ``encode_pallas``: returns (4^ndim, rows, 128) f32."""
    nwords, rows, lanes = payload.shape
    assert nwords == ref.payload_words(ndim, planes) and lanes == LANES
    assert emax.shape == (rows, LANES), emax.shape
    call = functools.partial(_decode_call, planes=planes, ndim=ndim)
    return dispatch(call, payload, emax)
