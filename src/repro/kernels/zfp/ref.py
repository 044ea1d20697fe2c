"""Pure-jnp oracle for the fixed-rate ZFP-style block codec.

This is the reference implementation that the Pallas TPU kernel
(``repro.kernels.zfp.kernel``) is validated against, and the numerical
ground truth for every compression feature in the framework (stencil
out-of-core streaming, compressed KV-cache offload, compressed activation
checkpointing, compressed gradient collectives).

Algorithm (per 4^d block, d in {1, 2, 3}), following cuZFP's fixed-rate
mode [Lindstrom, TVCG 2014] adapted for TPU:

  1. block-floating-point: extract the max base-2 exponent ``emax`` of the
     block and convert every value to a two's-complement fixed-point
     integer ``q = rint(x * 2^(FRAC - emax))`` with ``|q| <= 2^FRAC``.
  2. decorrelate with an *exactly invertible* integer lifting transform
     (two-level Haar / S-transform) applied along each of the d axes.
     cuZFP uses a slightly different non-orthogonal lift; ours is chosen
     so that the transform itself is lossless in integer arithmetic,
     which gives clean error bounds (all loss comes from steps 1 and 4).
  3. map signed coefficients to unsigned *negabinary* so that magnitude
     decays monotonically with bit position across sign changes.
  4. fixed-rate truncation: keep the top ``planes`` bit-planes of every
     coefficient and bit-pack them plane-major into uint32 words.
     (cuZFP additionally embeds group-test bits so a stream can be cut at
     any bit; in fixed-rate mode plane-truncation is equivalent and
     branch-free, which is exactly what a TPU wants. It also makes the
     sequency reordering of cuZFP a no-op, so we drop it.)

Rate accounting: ``planes`` bits per value + 16 bits per block of ``emax``
header.  The paper's f64 rates 32/64 and 24/64 correspond to
``planes=32, 24`` with ``dtype=float64``; the TPU-native f32 path uses
``planes=16, 12, 8`` for the same compression ratios.

Error model (see tests/test_zfp_properties.py):
  abs error <= 2^(emax - FRAC) + 2^(emax + GROWTH + 1 - planes)
where GROWTH = d (one doubling per lifted axis) — i.e. the error is a
bounded fraction of the *block maximum*, the fixed-rate analogue of a
pointwise relative bound.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Fixed-point fraction bits: chosen so that x * 2^shift is exact in the
# source float format (power-of-two scaling is exact) and the transform's
# worst-case growth of 2^d still fits the integer type with a guard bit.
_FRAC = {jnp.dtype(jnp.float32): 26, jnp.dtype(jnp.float64): 55}
_ITYPE = {jnp.dtype(jnp.float32): jnp.int32, jnp.dtype(jnp.float64): jnp.int64}
_UTYPE = {jnp.dtype(jnp.float32): jnp.uint32, jnp.dtype(jnp.float64): jnp.uint64}
_WIDTH = {jnp.dtype(jnp.float32): 32, jnp.dtype(jnp.float64): 64}

# Most negative exponent we honour before flushing a block to zero; keeps
# every 2^shift a *normal* number in the source float format.
_EMAX_FLOOR = {jnp.dtype(jnp.float32): -90, jnp.dtype(jnp.float64): -900}

_EXP_BIAS = {jnp.dtype(jnp.float32): 127, jnp.dtype(jnp.float64): 1023}
_MANT_BITS = {jnp.dtype(jnp.float32): 23, jnp.dtype(jnp.float64): 52}


def exp2i(shift: jax.Array, dtype) -> jax.Array:
    """Exact 2^shift for integer shift, built from IEEE-754 bits.

    Used instead of ``jnp.exp2`` so that the fixed-point scaling is
    bit-exact and the Pallas kernel matches this oracle exactly.
    """
    dt = jnp.dtype(dtype)
    it = _ITYPE[dt]
    bits = (shift.astype(it) + _EXP_BIAS[dt]) << _MANT_BITS[dt]
    return lax.bitcast_convert_type(bits, dt)

WORD_BITS = 32  # payload word size (uint32), both on TPU and host.
HEADER_BITS = 16  # per-block emax header, counted in reported ratios.


def block_size(ndim: int) -> int:
    return 4**ndim


# --- static subband rate allocation -----------------------------------
#
# cuZFP's embedded bit-plane stream spends fewer bits on subbands whose
# leading planes are all zero (data-dependent group testing — the
# sequential part the paper complains about in cuSZ). We replace it with
# a *static* allocation: low-frequency subbands get more planes, high-
# frequency fewer, with per-level offsets chosen so the total is exactly
# ``block_size * planes`` bits (same fixed rate, branch-free, static
# packing schedule — ideal for the TPU VPU). On smooth fields this
# recovers most of ZFP's rate-distortion advantage over uniform
# truncation (see tests/test_zfp_properties.py monotonicity and the
# fig7 reproduction).
#
# Per-axis Haar level of coefficient index [ss, ds, d0, d1] = [0,1,2,2];
# block level L = sum over axes. Offsets per L (sum_L n_L * delta_L = 0):

_SUBBAND_DELTA = {
    1: (2, 0, -1),
    2: (3, 2, 1, -1, -2),
    3: (5, 4, 2, 1, 0, -2, -3),
}
_AXIS_LEVEL = (0, 1, 2, 2)


@functools.lru_cache(maxsize=None)
def coeff_levels(ndim: int) -> Tuple[int, ...]:
    """Subband level of each coefficient in the (nb, 4^ndim) layout."""
    n = block_size(ndim)
    levels = []
    for i in range(n):
        lv, rem = 0, i
        for _ in range(ndim):
            lv += _AXIS_LEVEL[rem % 4]
            rem //= 4
        levels.append(lv)
    return tuple(levels)


@functools.lru_cache(maxsize=None)
def subband_planes(planes: int, ndim: int, width: int) -> Tuple[int, ...]:
    """Per-coefficient plane counts; sums to exactly block_size*planes.

    Subband offsets are only applied where no clipping at [0, width] can
    occur (4 <= planes <= width-5), so the fixed rate is always exact;
    outside that range allocation is uniform (= plain truncation)."""
    levels = coeff_levels(ndim)
    if 4 <= planes <= width - 5:
        delta = _SUBBAND_DELTA[ndim]
        return tuple(planes + delta[lv] for lv in levels)
    return tuple(min(width, planes) for _ in levels)


@functools.lru_cache(maxsize=None)
def level_order(planes: int, ndim: int, width: int):
    """Static stream order: coefficients sorted by descending plane
    count (stable). Returns (perm, inv_perm, prefix_counts) where
    prefix_counts[j] = #coefficients contributing a bit to plane j.
    With this order every plane's contributors are a *prefix*, so both
    packing and the Pallas kernel use static slices (no gathers)."""
    pv = subband_planes(planes, ndim, width)
    n = block_size(ndim)
    perm = tuple(sorted(range(n), key=lambda i: (-pv[i], i)))
    inv = [0] * n
    for pos, i in enumerate(perm):
        inv[i] = pos
    nplanes = max(pv) if pv else 0
    counts = tuple(sum(1 for i in range(n) if pv[i] > j) for j in range(nplanes))
    return perm, tuple(inv), counts


def payload_bits(ndim: int, planes: int, width: int = 32) -> int:
    return sum(subband_planes(planes, ndim, width))


def payload_words(ndim: int, planes: int, width: int = 32) -> int:
    """uint32 words per block of packed payload."""
    return -(-payload_bits(ndim, planes, width) // WORD_BITS)


def bits_per_value(ndim: int, planes: int, width: int = 32) -> float:
    """Achieved rate including the emax header."""
    n = block_size(ndim)
    return payload_bits(ndim, planes, width) / n + HEADER_BITS / n


# ---------------------------------------------------------------------------
# Fixed point <-> float
# ---------------------------------------------------------------------------


def _exponent(x: jax.Array) -> jax.Array:
    """frexp-style exponent (|x| < 2^e) from the IEEE-754 bits. Zeros
    and denormals get the smallest normal exponent, below
    ``_EMAX_FLOOR``, so a block of them clamps to the floor. Bit
    arithmetic (no libm) lowers the same way in XLA and in Mosaic."""
    dt = jnp.dtype(x.dtype)
    bits = lax.bitcast_convert_type(x, _ITYPE[dt])
    raw = (bits >> _MANT_BITS[dt]) & (2 * _EXP_BIAS[dt] + 1)
    return (raw - (_EXP_BIAS[dt] - 1)).astype(jnp.int32)


def block_emax(xb: jax.Array) -> jax.Array:
    """Max exponent per block. xb: coefficient-major (N, ...) float ->
    (...) int32."""
    dt = jnp.dtype(xb.dtype)
    e = jnp.max(_exponent(xb), axis=0)
    return jnp.maximum(e, _EMAX_FLOOR[dt])


def to_fixedpoint(xb: jax.Array, emax: jax.Array) -> jax.Array:
    dt = jnp.dtype(xb.dtype)
    shift = (_FRAC[dt] - emax).astype(jnp.int32)
    scaled = xb * exp2i(shift, dt)[None]
    return jnp.rint(scaled).astype(_ITYPE[dt])


def from_fixedpoint(q: jax.Array, emax: jax.Array, dtype) -> jax.Array:
    dt = jnp.dtype(dtype)
    shift = (emax - _FRAC[dt]).astype(jnp.int32)
    return q.astype(dt) * exp2i(shift, dt)[None]


# ---------------------------------------------------------------------------
# Integer lifting transform (exactly invertible)
# ---------------------------------------------------------------------------


def _s_fwd(u: jax.Array, v: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """S-transform butterfly: lossless integer average/difference."""
    return (u + v) >> 1, u - v


def _s_inv(s: jax.Array, d: jax.Array) -> Tuple[jax.Array, jax.Array]:
    u = s + ((d + 1) >> 1)
    return u, u - d


def _lift4_fwd(q0, q1, q2, q3):
    """Two-level Haar lift of four coefficient slabs."""
    s0, d0 = _s_fwd(q0, q1)
    s1, d1 = _s_fwd(q2, q3)
    ss, ds = _s_fwd(s0, s1)
    return ss, ds, d0, d1


def _lift4_inv(ss, ds, d0, d1):
    s0, s1 = _s_inv(ss, ds)
    q0, q1 = _s_inv(s0, d0)
    q2, q3 = _s_inv(s1, d1)
    return q0, q1, q2, q3


def _apply_per_axis(q: jax.Array, ndim: int, fn, reverse: bool) -> jax.Array:
    """Apply a size-4 lift along each in-block axis of a coefficient-
    major ``q`` (N, ...): the coefficient axis splits into ``ndim`` axes
    of 4, the first spatial axis the most significant. The inverse must
    visit axes in the opposite order to undo the forward exactly."""
    n, rest = q.shape[0], q.shape[1:]
    q = q.reshape((4,) * ndim + rest)
    axes = range(ndim)
    for ax in (reversed(axes) if reverse else axes):
        parts = [lax.index_in_dim(q, j, ax, keepdims=False) for j in range(4)]
        q = jnp.stack(fn(*parts), axis=ax)
    return q.reshape((n,) + rest)


def fwd_transform(q: jax.Array, ndim: int) -> jax.Array:
    return _apply_per_axis(q, ndim, _lift4_fwd, reverse=False)


def inv_transform(c: jax.Array, ndim: int) -> jax.Array:
    return _apply_per_axis(c, ndim, _lift4_inv, reverse=True)


# ---------------------------------------------------------------------------
# Negabinary + fixed-rate plane truncation
# ---------------------------------------------------------------------------


def _nb_mask(dt) -> int:
    w = _WIDTH[dt]
    return int(sum(1 << b for b in range(1, w, 2)))  # 0xAAAA...


def to_negabinary(c: jax.Array) -> jax.Array:
    dt = jnp.dtype(
        jnp.float32 if c.dtype == jnp.int32 else jnp.float64
    )
    ut = _UTYPE[dt]
    m = jnp.array(_nb_mask(dt), dtype=ut)
    cu = lax.bitcast_convert_type(c, ut)
    return (cu + m) ^ m


def from_negabinary(u: jax.Array) -> jax.Array:
    dt = jnp.dtype(jnp.float32 if u.dtype == jnp.uint32 else jnp.float64)
    ut, it = _UTYPE[dt], _ITYPE[dt]
    m = jnp.array(_nb_mask(dt), dtype=ut)
    return lax.bitcast_convert_type((u ^ m) - m, it)


def plane_masks(planes: int, ndim: int, width: int) -> Tuple[int, ...]:
    """Keep-masks implementing the subband allocation."""
    pv = subband_planes(int(planes), ndim, width)
    return tuple(
        (((1 << p) - 1) << (width - p)) if p > 0 else 0 for p in pv
    )


def truncate_planes(u: jax.Array, planes: int, ndim: int) -> jax.Array:
    """Keep the subband-allocated top planes of each coefficient of a
    coefficient-major ``u`` (N, ...)."""
    w = 32 if u.dtype == jnp.uint32 else 64
    pv = subband_planes(int(planes), ndim, w)
    if all(p >= w for p in pv):
        return u
    masks = jnp.array(plane_masks(planes, ndim, w), dtype=u.dtype)
    return u & masks.reshape((-1,) + (1,) * (u.ndim - 1))


# ---------------------------------------------------------------------------
# Bit-plane packing (plane-major, like the ZFP stream layout)
# ---------------------------------------------------------------------------


def _rows(a: jax.Array, order) -> jax.Array:
    """Rows of ``a`` in a static order: static slices, no gather (the
    Pallas kernels run this code too)."""
    return jnp.concatenate([a[i : i + 1] for i in order], axis=0)


def pack_planes(u: jax.Array, planes: int, ndim: int) -> jax.Array:
    """u: coefficient-major (N, ...) uintW. Returns (W, ...) uint32
    payload words: plane-major over the level-sorted coefficient order
    (the ZFP stream layout with static subband allocation). Plane j
    carries the first ``counts[j]`` coefficients of that order, which
    are exactly the bits ``truncate_planes`` keeps, so ``u`` need not
    be truncated first."""
    rest = u.shape[1:]
    w = 32 if u.dtype == jnp.uint32 else 64
    perm, _, counts = level_order(int(planes), ndim, w)
    nwords = payload_words(ndim, planes, w)
    if not nwords:
        return jnp.zeros((0,) + rest, jnp.uint32)
    up = _rows(u, perm)
    segs = [
        ((up[:k] >> (w - 1 - j)) & 1).astype(jnp.uint32)
        for j, k in enumerate(counts)
    ]
    pad = nwords * WORD_BITS - sum(counts)
    if pad:
        segs.append(jnp.zeros((pad,) + rest, jnp.uint32))
    flat = jnp.concatenate(segs, axis=0).reshape((nwords, WORD_BITS) + rest)
    words = flat[:, 0]
    for b in range(1, WORD_BITS):
        words = words | (flat[:, b] << b)
    return words


def unpack_planes(
    words: jax.Array, planes: int, ndim: int, dtype
) -> jax.Array:
    """Inverse of pack_planes: (W, ...) uint32 -> coefficient-major
    (N, ...) uintW (low planes zero)."""
    dt = jnp.dtype(dtype)
    ut, w = _UTYPE[dt], _WIDTH[dt]
    rest = words.shape[1:]
    n = block_size(ndim)
    _, inv, counts = level_order(int(planes), ndim, w)
    up = jnp.zeros((n,) + rest, dtype=ut)
    if not counts:
        return up
    bits = jnp.stack([(words >> b) & 1 for b in range(WORD_BITS)], axis=1)
    bits = bits.reshape((-1,) + rest)
    pos = 0
    for j, k in enumerate(counts):
        seg = bits[pos : pos + k].astype(ut) << (w - 1 - j)
        pos += k
        if k < n:
            seg = jnp.concatenate([seg, jnp.zeros((n - k,) + rest, ut)])
        up = up | seg
    return _rows(up, inv)


# ---------------------------------------------------------------------------
# Whole-codec entry points on blockified data
# ---------------------------------------------------------------------------


def encode_blocks(
    xb: jax.Array, planes: int, ndim: int
) -> Tuple[jax.Array, jax.Array]:
    """xb: coefficient-major (4^ndim, ...) float32/float64 -> (payload
    (W, ...) uint32, emax (...) int32)."""
    emax = block_emax(xb)
    q = to_fixedpoint(xb, emax)
    u = to_negabinary(fwd_transform(q, ndim))
    return pack_planes(u, planes, ndim), emax


def decode_blocks(
    payload: jax.Array, emax: jax.Array, planes: int, ndim: int, dtype
) -> jax.Array:
    u = unpack_planes(payload, planes, ndim, dtype)
    c = from_negabinary(u)
    q = inv_transform(c, ndim)
    return from_fixedpoint(q, emax, dtype)


def quantize_blocks(xb: jax.Array, planes: int, ndim: int) -> jax.Array:
    """decode(encode(x)) fused, skipping bit packing (numerics only).
    Must equal decode_blocks(*encode_blocks(...)) bit-for-bit."""
    emax = block_emax(xb)
    q = to_fixedpoint(xb, emax)
    c = fwd_transform(q, ndim)
    u = truncate_planes(to_negabinary(c), planes, ndim)
    c2 = from_negabinary(u)
    q2 = inv_transform(c2, ndim)
    return from_fixedpoint(q2, emax, xb.dtype)


# ---------------------------------------------------------------------------
# N-d array <-> blocks
# ---------------------------------------------------------------------------


def _padded_shape(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    return tuple(-(-s // 4) * 4 for s in shape)


def blockify(x: jax.Array, ndim: int) -> jax.Array:
    """x: (..., s1..s_ndim) -> coefficient-major (4^ndim, nb), edge-
    padded to x4.

    Leading axes are batch; the trailing ``ndim`` axes are the spatial
    axes that 4^ndim blocks tile. Blocks are numbered row-major over
    (batch..., block indices...); coefficient ``i`` of a block is its
    in-block offset in base 4, the first spatial axis most significant.

    The layout change runs in two stages so that no intermediate has a
    minor dimension of 4, which a TPU would pad to 128 lanes (32x its
    size in HBM): first the in-block offsets of every spatial axis but
    the last move to the front, then those of the last (minor) axis.
    """
    spatial = x.shape[-ndim:]
    padded = _padded_shape(spatial)
    pads = [(0, 0)] * (x.ndim - ndim) + [
        (0, p - s) for s, p in zip(spatial, padded)
    ]
    if any(p != (0, 0) for p in pads):
        x = jnp.pad(x, pads, mode="edge")
    batch = x.shape[: x.ndim - ndim]
    nbat, k = len(batch), ndim - 1
    nblocks = [p // 4 for p in padded]
    x = x.reshape(
        batch + sum(((n, 4) for n in nblocks[:-1]), start=()) + padded[-1:]
    )
    digits = [nbat + 2 * i + 1 for i in range(k)]
    blocks = [nbat + 2 * i for i in range(k)]
    x = x.transpose(digits + list(range(nbat)) + blocks + [x.ndim - 1])
    x = x.reshape(x.shape[:-1] + (nblocks[-1], 4))
    x = x.transpose(
        list(range(k)) + [x.ndim - 1] + list(range(k, x.ndim - 1))
    )
    return x.reshape(block_size(ndim), -1)


def unblockify(
    xb: jax.Array, shape: Tuple[int, ...], ndim: int
) -> jax.Array:
    """Inverse of blockify back to ``shape`` (crops the x4 padding),
    in the same two stages reversed."""
    spatial = shape[-ndim:]
    padded = _padded_shape(spatial)
    batch = tuple(shape[: len(shape) - ndim])
    nbat, k = len(batch), ndim - 1
    nblocks = [p // 4 for p in padded]
    x = xb.reshape((4,) * ndim + batch + tuple(nblocks))
    x = x.transpose(list(range(k)) + list(range(ndim, x.ndim)) + [k])
    x = x.reshape(x.shape[:-2] + padded[-1:])
    order = [k + i for i in range(nbat)]
    for i in range(k):
        order += [k + nbat + i, i]
    x = x.transpose(order + [x.ndim - 1]).reshape(batch + padded)
    slices = tuple(slice(None) for _ in batch) + tuple(
        slice(0, s) for s in spatial
    )
    return x[slices]


# ---------------------------------------------------------------------------
# High-level array API
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Compressed:
    """A fixed-rate compressed array (payload + per-block exponents)."""

    payload: jax.Array  # (W, nb) uint32, word-major
    emax: jax.Array  # (nb,) int32
    shape: Tuple[int, ...]
    planes: int
    ndim_spatial: int
    dtype: str

    def tree_flatten(self):
        return (self.payload, self.emax), (
            self.shape,
            self.planes,
            self.ndim_spatial,
            self.dtype,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        payload, emax = children
        return cls(payload, emax, *aux)

    @property
    def compression_ratio(self) -> float:
        raw_bits = 8 * jnp.dtype(self.dtype).itemsize
        return raw_bits / bits_per_value(self.ndim_spatial, self.planes)

    def nbytes(self) -> int:
        return int(self.payload.size * 4 + self.emax.size * 2)


def compress(x: jax.Array, planes: int, ndim: int = 3) -> Compressed:
    xb = blockify(x, ndim)
    payload, emax = encode_blocks(xb, planes, ndim)
    return Compressed(
        payload, emax, tuple(x.shape), planes, ndim, str(x.dtype)
    )


def decompress(c: Compressed) -> jax.Array:
    xb = decode_blocks(
        c.payload, c.emax, c.planes, c.ndim_spatial, jnp.dtype(c.dtype)
    )
    return unblockify(xb, c.shape, c.ndim_spatial)


def quantize(x: jax.Array, planes: int, ndim: int = 3) -> jax.Array:
    """Numerics of a compress->decompress round trip, without packing."""
    xb = blockify(x, ndim)
    return unblockify(quantize_blocks(xb, planes, ndim), x.shape, ndim)


def max_abs_error_bound(emax: jax.Array, planes: int, ndim: int, dtype):
    """Per-block worst-case absolute error (see module docstring)."""
    dt = jnp.dtype(dtype)
    frac = _FRAC[dt]
    w = _WIDTH[dt]
    quant = jnp.exp2((emax - frac).astype(dt))
    # negabinary truncation: the worst-allocated subband keeps
    # min(subband_planes) planes; dropped bits sum to < 2^(w-pmin+1)
    # fixed-point units, amplified by the inverse transform by < 2^ndim
    # (plus 1 rounding unit per lifting stage, absorbed in the +1).
    pmin = min(subband_planes(int(planes), ndim, w))
    trunc = jnp.exp2((emax + (w - pmin) + 1 + ndim - frac).astype(dt)) * (
        1 if pmin < w else 0
    )
    return quant * (2**ndim) + trunc
