"""Codec micro-benchmarks (paper §IV concern: codec overhead must not
outweigh the transfer saving).

XLA-compiled oracle throughput on this host CPU (1 core) + the achieved
compression ratios; the Pallas kernel is interpret-mode here (semantics
validation, not speed) so its row is tagged accordingly. The TPU
projection used by the pipeline model is derived in EXPERIMENTS.md.
"""

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.kernels.zfp import ops, ref


def run() -> None:
    key = jax.random.PRNGKey(0)
    vol = jax.random.normal(key, (64, 64, 64), jnp.float32)
    raw = vol.size * 4
    for planes in (16, 12, 8):
        comp = jax.jit(
            lambda x: ops.compress(x, planes=planes, ndim=3)
        )
        c0 = comp(vol)
        us = time_fn(comp, vol)
        ratio = 32.0 / ref.bits_per_value(3, planes)
        emit(
            f"codec/encode3d/rate{planes}_32",
            us,
            f"{raw/us*1e6/1e9:.2f}GB/s ratio={ratio:.2f}",
        )
        dec = jax.jit(ops.decompress)
        us = time_fn(dec, c0)
        emit(
            f"codec/decode3d/rate{planes}_32",
            us,
            f"{raw/us*1e6/1e9:.2f}GB/s",
        )
    # quantize (fused numerics path used by remat/grad compression)
    q = jax.jit(lambda x: ops.quantize(x, planes=12, ndim=1))
    flat = vol.reshape(-1)
    us = time_fn(q, flat)
    emit("codec/quantize1d/rate12_32", us, f"{raw/us*1e6/1e9:.2f}GB/s")
    # pallas kernel (interpret mode on a CPU: correctness vehicle, not
    # speed)
    enc = lambda: ops.compress(vol, planes=12, ndim=3, backend="pallas")
    us = time_fn(lambda: jax.block_until_ready(enc()))
    emit("codec/pallas_encode3d_interpret/rate12_32", us,
         "interpret-mode (semantics only)")
