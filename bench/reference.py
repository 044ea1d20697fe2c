"""The benchmark's own yardstick: seeded inputs and the plain reference.

Nothing here imports the program under test, so a change to the
program cannot change what it is compared with.

* ``fields`` makes the initial pressure and the velocity field of a box
  of the volume from the seed: a Ricker-like pulse in the volume centre
  and a vertical gradient plus three plane waves with seeded
  wavenumbers and phases. Both are elementwise functions of global
  coordinates, so a box is bit-identical to the same points of the
  whole volume.
* ``wave_step`` is the 25-point 8th-order acoustic update in plain
  ``jax.numpy`` (Shen et al. 2021, section VI).
* ``quantize`` is the fixed-rate block codec's round trip, written out
  on 4x4x4 blocks: block-floating-point with 26 fraction bits, a two-
  level integer Haar lift along each axis, negabinary, and the top bit
  planes of each coefficient kept by a static subband allocation. That
  is the number format the experiment codes store their compressed
  fields in.
* ``run_reference`` advances a box through the experiment code's
  rounds: ``bt`` steps, then every field the configuration compresses
  goes through ``quantize``. Blocks are aligned to the global 4-grid,
  so a unit-by-unit codec and this whole-box one give the same values.

The copies follow ``kernels/stencil/ref.py``, ``kernels/zfp/ref.py``
and ``chip_smoke.py`` of the repository as they stood when the
benchmark was written.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HALO = 4  # stencil radius (8th order)
C0 = -205.0 / 72.0
C = (8.0 / 5.0, -1.0 / 5.0, 8.0 / 315.0, -1.0 / 560.0)

Box = Tuple[Tuple[int, int], Tuple[int, int], Tuple[int, int]]


# ---------------------------------------------------------------------------
# seeded inputs
# ---------------------------------------------------------------------------


def waves(seed: int) -> np.ndarray:
    """(3, 4) float32: three plane waves of the velocity field, each a
    wavenumber triple and a phase."""
    rng = np.random.default_rng(seed)
    return np.asarray(
        [list(rng.uniform(1, 4, 3)) + [rng.uniform(0, 2 * np.pi)]
         for _ in range(3)], dtype=np.float32)


@functools.partial(jax.jit, static_argnames=("shape", "size"))
def _make(origin, waves, *, shape, size):
    axes = [jnp.arange(m, dtype=jnp.float32) + origin[i].astype(jnp.float32)
            for i, m in enumerate(size)]
    c = [a - (n - 1) / 2 for a, n in zip(axes, shape)]
    r2 = (
        c[0][:, None, None] ** 2 + c[1][None, :, None] ** 2
        + c[2][None, None, :] ** 2
    ) / (max(shape) / 8) ** 2
    p = (1.0 - 2.0 * r2) * jnp.exp(-r2)
    zz, yy, xx = (
        (a / n).reshape([-1 if i == j else 1 for j in range(3)])
        for i, (a, n) in enumerate(zip(axes, shape))
    )
    v = 0.04 + 0.03 * zz
    for w in range(3):
        k0, k1, k2, phase = (waves[w, j] for j in range(4))
        v = v + 0.01 * jnp.sin(
            2 * np.pi * (k0 * zz + k1 * yy + k2 * xx) + phase
        )
    return p, jnp.broadcast_to(v, p.shape)


def fields(shape: Sequence[int], seed: int, box: Optional[Box] = None):
    """(pressure, vel2) over ``box`` (default: the whole volume) of a
    volume of ``shape``, on the device, in one jitted call. The seed and
    the box's origin are arguments of the program, so one compiled
    program serves every seed."""
    shape = tuple(int(n) for n in shape)
    box = tuple((0, n) for n in shape) if box is None else tuple(
        (int(lo), int(hi)) for lo, hi in box)
    origin = np.asarray([lo for lo, _ in box], dtype=np.int32)
    size = tuple(hi - lo for lo, hi in box)
    return _make(origin, waves(seed), shape=shape, size=size)


# ---------------------------------------------------------------------------
# the stencil
# ---------------------------------------------------------------------------


def pad_bc(u):
    return jnp.pad(u, HALO)


def laplacian8(up):
    h = HALO
    c = up[h:-h, h:-h, h:-h]
    lap = 3.0 * C0 * c
    for k, ck in enumerate(C, start=1):
        lap = lap + ck * (
            up[h + k : up.shape[0] - h + k, h:-h, h:-h]
            + up[h - k : up.shape[0] - h - k, h:-h, h:-h]
            + up[h:-h, h + k : up.shape[1] - h + k, h:-h]
            + up[h:-h, h - k : up.shape[1] - h - k, h:-h]
            + up[h:-h, h:-h, h + k : up.shape[2] - h + k]
            + up[h:-h, h:-h, h - k : up.shape[2] - h - k]
        )
    return lap


def wave_step(p_prev, p_cur, vel2):
    """One step with zero boundary: interior (p_prev, p_cur, vel2) ->
    p_next. ``p_next = 2 p_cur - p_prev + vel2 * lap8(p_cur)``."""
    return 2.0 * p_cur - p_prev + vel2 * laplacian8(pad_bc(p_cur))


# ---------------------------------------------------------------------------
# the fixed-rate block codec's round trip (float32 fields)
# ---------------------------------------------------------------------------

_FRAC = 26  # fixed-point fraction bits
_EMAX_FLOOR = -90
_AXIS_LEVEL = (0, 1, 2, 2)  # Haar level of in-axis coefficients ss, ds, d0, d1
_SUBBAND_DELTA = (5, 4, 2, 1, 0, -2, -3)  # planes offset per block level


def _exp2i(shift):
    bits = (shift.astype(jnp.int32) + 127) << 23
    return lax.bitcast_convert_type(bits, jnp.float32)


def _lift_fwd(q0, q1, q2, q3):
    s0, d0 = (q0 + q1) >> 1, q0 - q1
    s1, d1 = (q2 + q3) >> 1, q2 - q3
    return (s0 + s1) >> 1, s0 - s1, d0, d1


def _lift_inv(ss, ds, d0, d1):
    def s_inv(s, d):
        u = s + ((d + 1) >> 1)
        return u, u - d

    s0, s1 = s_inv(ss, ds)
    q0, q1 = s_inv(s0, d0)
    q2, q3 = s_inv(s1, d1)
    return q0, q1, q2, q3


def _along(b, axis, fn):
    parts = [lax.index_in_dim(b, j, axis, keepdims=False) for j in range(4)]
    return jnp.stack(fn(*parts), axis=axis)


def _plane_masks(planes: int) -> np.ndarray:
    """(4, 4, 4) keep-masks of the 32-bit negabinary coefficients."""
    lv = np.add.outer(np.add.outer(_AXIS_LEVEL, _AXIS_LEVEL), _AXIS_LEVEL)
    if 4 <= planes <= 27:
        keep = planes + np.asarray(_SUBBAND_DELTA)[lv]
    else:
        keep = np.full(lv.shape, min(32, planes))
    masks = [((1 << int(p)) - 1) << (32 - int(p)) if p > 0 else 0
             for p in keep.ravel()]
    return np.asarray(masks, dtype=np.uint32).reshape(4, 4, 4)


@functools.partial(jax.jit, static_argnames=("planes",))
def quantize(x, *, planes: int):
    """The codec's round trip on a float32 (Z, Y, X) array whose sides
    are multiples of 4, block by 4x4x4 block."""
    z, y, x_ = x.shape
    b = x.reshape(z // 4, 4, y // 4, 4, x_ // 4, 4).transpose(0, 2, 4, 1, 3, 5)
    bits = lax.bitcast_convert_type(b, jnp.int32)
    expo = ((bits >> 23) & 255) - 126
    emax = jnp.maximum(jnp.max(expo, axis=(3, 4, 5), keepdims=True),
                       _EMAX_FLOOR)
    q = jnp.rint(b * _exp2i(_FRAC - emax)).astype(jnp.int32)
    for axis in (3, 4, 5):
        q = _along(q, axis, _lift_fwd)
    m = jnp.uint32(0xAAAAAAAA)
    u = (lax.bitcast_convert_type(q, jnp.uint32) + m) ^ m
    u = u & jnp.asarray(_plane_masks(planes))
    q = lax.bitcast_convert_type((u ^ m) - m, jnp.int32)
    for axis in (5, 4, 3):
        q = _along(q, axis, _lift_inv)
    out = q.astype(jnp.float32) * _exp2i(emax - _FRAC)
    return out.transpose(0, 3, 1, 4, 2, 5).reshape(z, y, x_)


# ---------------------------------------------------------------------------
# the reference run
# ---------------------------------------------------------------------------


def _keep(a, planes: Optional[int]):
    """A field as the experiment code stores it between rounds."""
    if planes is None:
        return a
    return quantize(a.astype(jnp.float32), planes=planes).astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "planes", "dtype"))
def _rounds(p_prev, p_cur, vel2, rounds, *, bt, planes, dtype):
    pp = _keep(p_prev.astype(dtype), planes["p_prev"])
    pc = _keep(p_cur.astype(dtype), planes["p_cur"])
    v = _keep(vel2.astype(dtype), planes["vel2"])

    def one(_, carry):
        pp, pc = carry
        for _ in range(bt):
            pp, pc = pc, wave_step(pp, pc, v)
        return _keep(pp, planes["p_prev"]), _keep(pc, planes["p_cur"])

    pp, pc = lax.fori_loop(0, rounds, one, (pp, pc))
    return pp.astype(jnp.float32), pc.astype(jnp.float32)


def cone(shape, region: Box, steps: int, grain: int = 128) -> Box:
    """A box that holds the dependency cone of ``region`` after
    ``steps`` steps (the region widened by HALO planes a step on every
    side, clipped at the walls of the volume), its sides rounded up to
    a multiple of ``grain`` or to the whole volume, so that runs whose
    regions and step counts differ a little share one compiled
    reference. A wider box holds the same exact values."""
    pad = HALO * steps
    out = []
    for (lo, hi), n in zip(region, shape):
        a, b = max(0, lo - pad), min(n, hi + pad)
        size = min(n, -(-(b - a) // grain) * grain)
        start = min(a, n - size)
        out.append((start, start + size))
    return tuple(out)


def run_reference(
    shape, seed: int, region: Box, rounds: int, bt: int,
    planes: Dict[str, Optional[int]], dtype=jnp.float32, grain: int = 128,
) -> Dict[str, np.ndarray]:
    """(p_prev, p_cur) over ``region`` after ``rounds`` rounds of ``bt``
    steps, started from the seeded fields with p_prev = p_cur.

    The reference runs in core over a box that holds the region's
    dependency cone (``cone``). Zero padding at a cut face of the box
    corrupts HALO planes a step, so after ``rounds * bt`` steps at most
    the cone's margin is corrupt and the region is exact; cut faces lie
    on the 4-grid, so the codec's blocks never mix the two. ``dtype`` is the precision the stencil
    runs in (the control runs it in bfloat16)."""
    box = cone(shape, region, rounds * bt, grain)
    for (lo, hi), (blo, bhi) in zip(region, box):
        if lo % 4 or hi % 4 or blo % 4 or bhi % 4:
            raise ValueError(f"region {region} / cone {box} off the 4-grid")
    p, v = fields(shape, seed, box)
    pp, pc = _rounds(p, p, v, rounds, bt=bt,
                     planes=_frozen(planes), dtype=jnp.dtype(dtype))
    crop = tuple(slice(lo - blo, hi - blo)
                 for (lo, hi), (blo, _) in zip(region, box))
    return {"p_prev": np.asarray(pp[crop]), "p_cur": np.asarray(pc[crop])}


class _frozen(dict):
    """A hashable mapping, for a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """max|got - want| / max|want|."""
    return float(np.max(np.abs(got.astype(np.float64) - want))
                 / np.max(np.abs(want)))
