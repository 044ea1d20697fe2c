"""VMEM-resident Mamba-1 selective-scan Pallas kernel.

The §Perf forward fix for falcon-mamba (EXPERIMENTS §4 Cell C
spillover): Mamba-1's per-(channel, state) decay defeats the SSD Gram
trick, and any XLA formulation writes the (S, D, N) state expansion to
HBM — 26 TB/device per train step. This kernel is the TPU analogue of
the original CUDA kernel's SRAM strategy: the (D-tile, N) state lives
in a VMEM accumulator while the sequence streams through in chunks, so
HBM traffic is only the layer's own activations:

  traffic = dt, x, B, C in + y out = O(B*S*(2D + 2N)) bytes
  vs O(B*S*D*N) for the unfused form — a ~N/2 = 8x cut at N=16.

Grid: (B, D-tiles, S-chunks), sequence innermost so the carried state
in the revisited h_ref is correct (Pallas iterates the last grid axis
fastest). Inside a chunk the recurrence is evaluated with a log-depth
associative scan over VREGs.

Validated in interpret mode against repro.models.ssm (which is itself
tested against a sequential reference) — tests/test_sscan_kernel.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.platform import dispatch


def _kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_ref,
            *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[...]

    dt = dt_ref[...][0]  # (c, Dt)
    x = x_ref[...][0]
    b_in = b_ref[...][0]  # (c, N)
    c_in = c_ref[...][0]
    a = a_ref[...]  # (Dt, N)
    h = h_ref[...][0]  # (Dt, N)
    decay = jnp.exp(dt[..., None] * a[None])  # (c, Dt, N)
    inp = dt[..., None] * b_in[:, None, :] * x[..., None]

    def comb(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    acum, bcum = lax.associative_scan(comb, (decay, inp), axis=0)
    h_chunk = acum * h[None] + bcum  # (c, Dt, N)
    y_ref[...] = jnp.einsum(
        "cdn,cn->cd", h_chunk, c_in, preferred_element_type=jnp.float32
    )[None]
    h_ref[...] = h_chunk[-1:][None][0]


@functools.partial(jax.jit, static_argnames=("chunk", "d_tile"))
def selective_scan_pallas(
    dt: jax.Array,  # (B, S, D) f32
    a: jax.Array,  # (D, N) f32
    b_in: jax.Array,  # (B, S, N) f32
    c_in: jax.Array,  # (B, S, N) f32
    x: jax.Array,  # (B, S, D) f32
    h0: jax.Array,  # (B, D, N) f32
    *,
    chunk: int = 64,
    d_tile: int = 256,
):
    """Returns (y (B,S,D), h_last (B,D,N))."""
    bsz, s, d = x.shape
    n = a.shape[1]
    assert s % chunk == 0 and d % d_tile == 0, (s, chunk, d, d_tile)
    grid = (bsz, d // d_tile, s // chunk)
    specs = dict(
        dt=pl.BlockSpec((1, chunk, d_tile), lambda b, di, ci: (b, ci, di)),
        bc=pl.BlockSpec((1, chunk, n), lambda b, di, ci: (b, ci, 0)),
        a=pl.BlockSpec((d_tile, n), lambda b, di, ci: (di, 0)),
        h=pl.BlockSpec((1, d_tile, n), lambda b, di, ci: (b, di, 0)),
    )
    call = functools.partial(
        pl.pallas_call,
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            specs["dt"], specs["dt"], specs["bc"], specs["bc"],
            specs["a"], specs["h"],
        ],
        out_specs=[specs["dt"], specs["h"]],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),
        ],
    )
    return dispatch(
        lambda *args, interpret: call(interpret=interpret)(*args),
        dt, x, b_in, c_in, a, h0,
    )
