"""jit'd public wrappers around the ZFP-style codec.

``backend="ref"`` runs the pure-jnp oracle (XLA-compiled, the numerics
ground truth). ``backend="pallas"`` runs the Pallas kernel: compiled
Mosaic on a TPU, interpret mode on any other platform
(``repro.kernels.platform``). Both produce bit-identical results
(tests/test_zfp_kernel.py).
"""

from __future__ import annotations

import functools
from typing import List, Literal, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from . import kernel, ref
from .ref import Compressed

Backend = Literal["ref", "pallas"]


def bucket_tile(nb: int) -> int:
    """Pallas tile size (blocks per grid step) for an ``nb``-block
    batch: 128 blocks (one lane row) times the next power of two rows,
    capped at ``DEFAULT_TILE_BLOCKS``.

    Bucketing bounds codec recompilation: the kernel compiles per
    (padded block count, planes, ndim), so small units of every size
    share the four tiles 128..1024, and larger ones pad to a multiple
    of ``DEFAULT_TILE_BLOCKS`` — at the cost of <2x padding waste on
    the last tile."""
    tile = kernel.LANES
    while tile < nb and tile < kernel.DEFAULT_TILE_BLOCKS:
        tile <<= 1
    return tile


def _to_tiles(xs: jax.Array) -> jax.Array:
    """Coefficient-major (N, nb) -> kernel tiles (N, rows, 128), zero-
    padded to a whole number of ``bucket_tile`` tiles."""
    n, nb = xs.shape
    tile = bucket_tile(nb)
    nbp = -(-nb // tile) * tile
    xs = jnp.pad(xs, ((0, 0), (0, nbp - nb)))
    return xs.reshape(n, nbp // kernel.LANES, kernel.LANES)


def _from_tiles(t: jax.Array, nb: int) -> jax.Array:
    """Inverse of ``_to_tiles``: (N, rows, 128) -> (N, nb)."""
    return t.reshape(t.shape[0], -1)[:, :nb]


# Blocks per step of the XLA codec on large units. Its bit-plane
# intermediates take ~3 KiB a block (one uint32 per payload bit), so an
# unchunked encode of a 128 x 1152 x 1152 unit needs more than a v5e's
# 16 GB of HBM; 2^16 blocks a step keep them near 200 MiB.
_REF_CHUNK = 1 << 16


def _ref_chunked(fn, *arrays):
    """``fn`` over coefficient-major arrays (minor axis = blocks), in
    ``_REF_CHUNK``-block steps of a ``fori_loop``. Every codec stage is
    per block, so the result is the unchunked one."""
    nb = arrays[0].shape[-1]
    if nb <= _REF_CHUNK:
        return fn(*arrays)
    nch = -(-nb // _REF_CHUNK)
    arrays = [
        jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, nch * _REF_CHUNK - nb)])
        for a in arrays
    ]
    take = lambda a, i: lax.dynamic_slice_in_dim(
        a, i * _REF_CHUNK, _REF_CHUNK, axis=a.ndim - 1
    )
    outs = jax.eval_shape(fn, *(take(a, 0) for a in arrays))
    outs = [
        jnp.zeros(o.shape[:-1] + (nch * _REF_CHUNK,), o.dtype)
        for o in jax.tree_util.tree_leaves(outs)
    ]

    def body(i, acc):
        got = jax.tree_util.tree_leaves(fn(*(take(a, i) for a in arrays)))
        return [
            lax.dynamic_update_slice_in_dim(
                o, g, i * _REF_CHUNK, axis=o.ndim - 1
            )
            for o, g in zip(acc, got)
        ]

    outs = [o[..., :nb] for o in lax.fori_loop(0, nch, body, outs)]
    return outs if len(outs) > 1 else outs[0]


@functools.partial(jax.jit, static_argnames=("planes", "ndim", "backend"))
def compress(
    x: jax.Array,
    *,
    planes: int,
    ndim: int = 3,
    backend: Backend = "ref",
) -> Compressed:
    """Fixed-rate compress the trailing ``ndim`` axes of ``x``."""
    xb = ref.blockify(x, ndim)
    nb = xb.shape[1]
    if backend == "pallas" and x.dtype == jnp.float32:
        payload, emax = kernel.encode_pallas(
            _to_tiles(xb), planes=planes, ndim=ndim
        )
        payload, emax = _from_tiles(payload, nb), _from_tiles(emax[None], nb)[0]
    else:
        payload, emax = _ref_chunked(
            lambda a: ref.encode_blocks(a, planes, ndim), xb
        )
    return Compressed(payload, emax, tuple(x.shape), planes, ndim, str(x.dtype))


@functools.partial(jax.jit, static_argnames=("backend",))
def decompress(c: Compressed, *, backend: Backend = "ref") -> jax.Array:
    dtype = jnp.dtype(c.dtype)
    if backend == "pallas" and dtype == jnp.float32:
        nb = c.emax.shape[0]
        xb = _from_tiles(
            kernel.decode_pallas(
                _to_tiles(c.payload), _to_tiles(c.emax[None])[0],
                planes=c.planes, ndim=c.ndim_spatial,
            ),
            nb,
        )
    else:
        xb = _ref_chunked(
            lambda p, e: ref.decode_blocks(
                p, e, c.planes, c.ndim_spatial, dtype
            ),
            c.payload, c.emax,
        )
    return ref.unblockify(xb, c.shape, c.ndim_spatial)


def compress_units(
    xs: Sequence[jax.Array],
    *,
    planes: Union[int, Sequence[Optional[int]]],
    ndim: int = 3,
    backend: Backend = "ref",
) -> List[Union[Compressed, jax.Array]]:
    """Batched encode: dispatch every unit's encoder before blocking on
    any payload.

    Each ``compress`` call is jit-compiled and asynchronously
    dispatched, so the returned ``Compressed`` handles are futures —
    the out-of-core executor ships (D2H) each unit as its encode
    finishes instead of synchronizing after the whole batch, and the
    host store seeds all units with a single dispatch burst.

    ``planes`` is either one rate for the whole batch, or a per-unit
    sequence (adaptive rate control): entry ``None`` skips the codec
    for that unit and passes the raw array through unchanged — the
    lossless path of ``RateController``.
    """
    if isinstance(planes, int):
        per_unit: List[Optional[int]] = [planes] * len(xs)
    else:
        per_unit = list(planes)
        if len(per_unit) != len(xs):
            raise ValueError(
                f"planes sequence length {len(per_unit)} != "
                f"{len(xs)} units"
            )
    return [
        x if p is None else compress(x, planes=p, ndim=ndim, backend=backend)
        for x, p in zip(xs, per_unit)
    ]


def decompress_units(
    cs: Sequence[Compressed],
    *,
    backend: Backend = "ref",
) -> List[jax.Array]:
    """Batched decode: dispatch every unit's decoder before blocking on
    any output — the counterpart of ``compress_units``.

    Each ``decompress`` call is already asynchronously dispatched; the
    batched entry point exists so callers decode a whole unit list in
    one burst *before* materializing any of it. That is what fixes
    ``HostUnitStore.gather``, which previously staged + decoded +
    ``np.asarray``'d one unit per loop iteration (a synchronous
    round-trip each). The executor's per-visit decode uses it too, for
    a single shared code path.
    """
    return [decompress(c, backend=backend) for c in cs]


@functools.partial(jax.jit, static_argnames=("planes", "ndim"))
def quantize(x: jax.Array, *, planes: int, ndim: int = 3) -> jax.Array:
    """Numerics of compress->decompress without materialising payload.

    Used where only the *precision effect* of on-the-fly compression
    matters (long precision-loss sweeps, compressed-remat numerics).
    """
    return ref.quantize(x, planes, ndim)


def compressed_nbytes(c: Compressed) -> int:
    return c.nbytes()
