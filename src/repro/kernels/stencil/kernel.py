"""Pallas TPU kernels for the 25-point acoustic stencil.

Tiling (TPU adaptation of the paper's CUDA kernel):

* The grid runs over (z-tiles, y-tiles) of ``_TZ x _TY`` output points.
  x stays whole in every tile, so a z-plane of a tile is a (y, x) array
  with y on the sublanes and x on the lanes. ``_TY`` and the y halo
  ``_HY`` are multiples of 8 and the lane extent a multiple of 128: the
  (8, 128) f32 tiling Mosaic requires.
* Each grid step reads an extended *window* of every input: the tile
  plus ``K = HALO * rungs`` planes in z and ``_HY`` rows in y on each
  side. Windows of neighbouring tiles overlap, so they are element-
  offset BlockSpecs (``pl.Element``); the Pallas pipeline prefetches
  the next window while the current one computes.
* x is stored circularly: interior at lanes [0, X), the right halo
  after it, the left halo in the last lanes, zeros between. An x shift
  is a lane rotation (``pltpu.roll``) that brings the halo in from the
  other end, a y shift a sublane rotation, a z shift a plane read.
  Zero-BC fields whose X fills whole lane tiles (1152 = 9 x 128) are
  not padded: the kernel masks the lanes that rotate past an edge.
* One rung (time step) walks the window a plane at a time in a
  ``fori_loop``; the unrolled body is one plane, so compile time does
  not grow with the tile. Rotation wrap-around and the window's cut
  edges pollute HALO planes/rows per rung from each side, so after
  ``rungs`` rungs the tile centre is exact.
* Per-element arithmetic is ``ref.wave_step``'s expression tree in the
  same order: every kernel is bit-identical to its oracle.

VMEM per grid step at X = 1152, two rungs: three double-buffered
32 x 32 x 1152 windows (28.3 MiB), two rung buffers (9.4 MiB) and two
double-buffered 16 x 16 x 1152 outputs (4.7 MiB), inside the 64 MiB
limit the launch sets. In HBM the zero-padded z/y window copies of the
three inputs are the temporaries (2.8 GB at a 160-plane visit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import dispatch

from .ref import C, C0, HALO

_TZ = 16  # output planes per grid step
_TY = 16  # output rows per grid step (multiple of 8)
_HY = 8  # y halo rows: the sublane tile, >= HALO * MAX_RUNGS
_LANES = 128
MAX_RUNGS = 2  # fused steps per launch; the y halo covers this many
_VMEM_LIMIT = 64 * 2**20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _rung(pp, pc, v, dst, lo, hi, lap_dst=None, mask_x=False):
    """One time step over window planes [lo, hi): ``dst[p]`` from
    ``pc[p-4 .. p+4]``, ``pp[p]`` and ``v[p]``. ``mask_x``: the lanes
    hold exactly the interior (no zero lanes to rotate in), so an x
    shift past either edge reads an explicit zero, the BC."""
    _, ye, xe = pc.shape
    if mask_x:
        lane = lax.broadcasted_iota(jnp.int32, (ye, xe), 1)

    def xshift(c, k):
        """(c at x+k, c at x-k)."""
        up, down = pltpu.roll(c, xe - k, 1), pltpu.roll(c, k, 1)
        if mask_x:
            up = jnp.where(lane < xe - k, up, 0.0)
            down = jnp.where(lane >= k, down, 0.0)
        return up, down

    def body(p, carry):
        c = pc[p]
        lap = 3.0 * C0 * c
        for k, ck in enumerate(C, start=1):
            xu, xd = xshift(c, k)
            lap = lap + ck * (
                pc[p + k]
                + pc[p - k]
                + pltpu.roll(c, ye - k, 0)
                + pltpu.roll(c, k, 0)
                + xu
                + xd
            )
        dst[p] = 2.0 * c - pp[p] + v[p] * lap
        if lap_dst is not None:
            lap_dst[p] = lap
        return carry

    lax.fori_loop(lo, hi, body, 0)


def _stencil_kernel(pp_ref, pc_ref, v_ref, out0, out1, s0, s1, *,
                    rungs: int, emit_lap: bool, mask_x: bool):
    e = pc_ref.shape[0]
    if emit_lap:  # one step: (p_next, lap)
        _rung(pp_ref, pc_ref, v_ref, s0, HALO, e - HALO, lap_dst=s1,
              mask_x=mask_x)
        finals = (s0, s1)
    else:  # ``rungs`` steps: (p_prev, p_cur) after them
        bufs = (pp_ref, pc_ref, s0, s1)
        for r in range(rungs):
            _rung(bufs[r], bufs[r + 1], v_ref, bufs[r + 2],
                  HALO * (r + 1), e - HALO * (r + 1), mask_x=mask_x)
        finals = (bufs[rungs], bufs[rungs + 1])
    kz = (e - _TZ) // 2
    for out, buf in zip((out0, out1), finals):
        out[...] = buf[kz : kz + _TZ, _HY : _HY + _TY, :]


def _launch(pp, pc, v, *, rungs: int, emit_lap: bool, mask_x: bool,
            interpret: bool):
    """Windowed inputs (Zp + 2K, Yp + 2*_HY, XE) -> two (Zp, Yp, XE)."""
    k = HALO * rungs
    zs, ys, xe = pc.shape
    nz, ny = (zs - 2 * k) // _TZ, (ys - 2 * _HY) // _TY
    window = pl.BlockSpec(
        (pl.Element(_TZ + 2 * k), pl.Element(_TY + 2 * _HY), pl.Element(xe)),
        lambda i, j: (i * _TZ, j * _TY, 0),
    )
    tile = pl.BlockSpec((_TZ, _TY, xe), lambda i, j: (i, j, 0))
    out = jax.ShapeDtypeStruct((nz * _TZ, ny * _TY, xe), pc.dtype)
    buf = pltpu.VMEM((_TZ + 2 * k, _TY + 2 * _HY, xe), pc.dtype)
    return pl.pallas_call(
        functools.partial(_stencil_kernel, rungs=rungs, emit_lap=emit_lap,
                          mask_x=mask_x),
        grid=(nz, ny),
        in_specs=[window] * 3,
        out_specs=[tile, tile],
        out_shape=[out, out],
        scratch_shapes=[buf, buf],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(pp, pc, v)


def _window(a, lo: int, k: int, zp: int, yp: int, xe: int):
    """Lay out ``a``, whose interior starts ``lo`` elements in on every
    axis (its halo), for ``_launch``: the interior starts ``k`` planes
    in along z and ``_HY`` rows in along y, zeros fill up to the tiled
    extents, and x is circular (the ``lo`` left-halo lanes move to the
    end)."""
    zs, ys, xs = a.shape
    a = jnp.pad(a, (
        (k - lo, zp + 2 * k - (zs + k - lo)),
        (_HY - lo, yp + 2 * _HY - (ys + _HY - lo)),
        (0, xe - xs),
    ))
    return jnp.roll(a, -lo, axis=2) if lo else a


def _tiled(z: int, y: int, x: int, xhalo: int):
    return _round_up(z, _TZ), _round_up(y, _TY), _round_up(x + xhalo, _LANES)


def _zero_bc_tiles(z: int, y: int, x: int):
    """Tiled extents for zero-BC inputs. An x that fills whole lane
    tiles is not padded (the kernel masks its edges instead), so a
    volume of whole tiles — the engine's 1152-wide planes — needs no
    crop of the outputs either."""
    zp, yp, _ = _tiled(z, y, x, 0)
    xe = x if x % _LANES == 0 else _round_up(x + HALO, _LANES)
    return zp, yp, xe


@jax.jit
def wave_step_pallas(p_prev: jax.Array, p_cur: jax.Array, vel2: jax.Array):
    """One acoustic step. p_prev/p_cur: padded (Z+8, Y+8, X+8) f32 (the
    halo may hold any values); vel2: interior (Z, Y, X). Returns
    (p_next, lap), both interior — the contract of ``ref.wave_step``."""
    z, y, x = vel2.shape
    assert p_cur.shape == p_prev.shape == (z + 2 * HALO, y + 2 * HALO,
                                           x + 2 * HALO), p_cur.shape
    zp, yp, xe = _tiled(z, y, x, 2 * HALO)
    args = [_window(a, HALO, HALO, zp, yp, xe) for a in (p_prev, p_cur)]
    args.append(_window(vel2, 0, HALO, zp, yp, xe))
    call = functools.partial(_launch, rungs=1, emit_lap=True, mask_x=False)
    p_next, lap = dispatch(call, *args)
    return p_next[:z, :y, :x], lap[:z, :y, :x]


@functools.partial(jax.jit, static_argnames=("steps",))
def wave_multistep_pallas(
    p_prev: jax.Array, p_cur: jax.Array, vel2: jax.Array, *, steps: int
):
    """``steps`` fused acoustic steps with zero BC, the contract of
    ``ref.ladder_steps``: all inputs interior (Z, Y, X) f32, returns
    interior (p_prev, p_cur) after ``steps`` steps. Any shape tiles
    (zero padding outside the volume stays zero, because vel2 is zero
    there, which is the BC). Each launch fuses up to ``MAX_RUNGS``
    steps with the intermediate rungs in VMEM."""
    z, y, x = p_cur.shape
    assert p_prev.shape == vel2.shape == (z, y, x)
    zp, yp, xe = _zero_bc_tiles(z, y, x)
    pp, pc = p_prev, p_cur
    done = 0
    while done < steps:
        rungs = min(MAX_RUNGS, steps - done)
        k = HALO * rungs
        args = [_window(a, 0, k, zp, yp, xe) for a in (pp, pc, vel2)]
        call = functools.partial(_launch, rungs=rungs, emit_lap=False,
                                 mask_x=xe == x)
        pp, pc = (o[:z, :y, :x] for o in dispatch(call, *args))
        done += rungs
    return pp, pc
