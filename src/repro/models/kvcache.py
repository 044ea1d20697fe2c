"""Fixed-rate compressed KV cache — the paper's separate-compression
idea applied to the decode memory boundary.

Layout mirrors the stencil engine's remainder/common split: the KV
sequence is stored as *compressed chunks* (4x4 ZFP blocks over
(seq, head_dim), independently addressable — new chunks append without
touching old ones, the exact dependency fix of paper §V-A) plus an
uncompressed *tail window* of the most recent tokens (the "common
region" still being written). Appending a token writes the tail; when
the tail fills a chunk, that chunk is encoded once and never revisited.

On real TPUs the decompress fuses into the attention kernel (VPU work
against an HBM-bound op); here the composition is XLA ops validated
against the raw cache within the codec tolerance
(tests/test_kvcache.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.zfp import ops as zfp_ops
from repro.kernels.zfp import ref as zfp_ref
from repro.models import layers as L

CHUNK = 64  # tokens per compressed chunk (16 seq-blocks of 4)


class CompressedKV(NamedTuple):
    """Single-layer compressed KV for a (B, S, KVH, D) cache."""

    payload_k: jax.Array  # (B, KVH, NB, W) uint32
    emax_k: jax.Array  # (B, KVH, NB) int32
    payload_v: jax.Array
    emax_v: jax.Array
    tail_k: jax.Array  # (B, CHUNK, KVH, D) raw
    tail_v: jax.Array
    length: jax.Array  # () total tokens


def _nb_per_chunk(head_dim: int) -> int:
    return (CHUNK // 4) * (head_dim // 4)


def init_compressed_kv(
    batch: int, max_len: int, kv_heads: int, head_dim: int, planes: int,
    dtype=jnp.bfloat16,
) -> CompressedKV:
    assert max_len % CHUNK == 0
    nchunks = max_len // CHUNK
    nb = nchunks * _nb_per_chunk(head_dim)
    w = zfp_ref.payload_words(2, planes)
    mk = lambda: jnp.zeros((batch, kv_heads, nb, w), jnp.uint32)
    me = lambda: jnp.zeros((batch, kv_heads, nb), jnp.int32)
    tail = lambda: jnp.zeros((batch, CHUNK, kv_heads, head_dim), dtype)
    return CompressedKV(
        mk(), me(), mk(), me(), tail(), tail(), jnp.int32(0)
    )


def _encode_chunk(x: jax.Array, planes: int):
    """x: (B, CHUNK, KVH, D) -> payload (B, KVH, nbc, W), emax."""
    b, c, kvh, d = x.shape
    xt = jnp.moveaxis(x, 2, 1).astype(jnp.float32)  # (B, KVH, CHUNK, D)
    comp = zfp_ops.compress(xt, planes=planes, ndim=2)
    nbc = _nb_per_chunk(d)
    payload = comp.payload.T.reshape(b, kvh, nbc, -1)
    emax = comp.emax.reshape(b, kvh, nbc)
    return payload, emax


def _decode_all(payload, emax, planes: int, seq: int, head_dim: int,
                dtype):
    """payload: (B, KVH, NB, W) -> (B, seq, KVH, D)."""
    b, kvh, nb, w = payload.shape
    c = zfp_ref.Compressed(
        payload.reshape(-1, w).T,
        emax.reshape(-1),
        (b * kvh, seq, head_dim),
        planes,
        2,
        "float32",
    )
    x = zfp_ops.decompress(c)  # (B*KVH, seq, D)
    x = x.reshape(b, kvh, seq, head_dim)
    return jnp.moveaxis(x, 1, 2).astype(dtype)  # (B, seq, KVH, D)


@functools.partial(jax.jit, static_argnames=("planes",))
def append_token(
    ckv: CompressedKV, k: jax.Array, v: jax.Array, *, planes: int
) -> CompressedKV:
    """k, v: (B, 1, KVH, D). Writes the tail; when the tail fills,
    encodes it as a new chunk (branchless: both paths computed, the
    cheap one selected — TPU-friendly)."""
    b, _, kvh, d = k.shape
    pos = ckv.length % CHUNK
    tail_k = jax.lax.dynamic_update_slice(
        ckv.tail_k, k.astype(ckv.tail_k.dtype), (0, pos, 0, 0)
    )
    tail_v = jax.lax.dynamic_update_slice(
        ckv.tail_v, v.astype(ckv.tail_v.dtype), (0, pos, 0, 0)
    )
    new_len = ckv.length + 1
    chunk_full = (new_len % CHUNK) == 0

    def flush(ckv, tk, tv):
        pk, ek = _encode_chunk(tk, planes)
        pv, ev = _encode_chunk(tv, planes)
        nbc = _nb_per_chunk(d)
        cidx = (new_len // CHUNK - 1) * nbc
        return ckv._replace(
            payload_k=jax.lax.dynamic_update_slice(
                ckv.payload_k, pk, (0, 0, cidx, 0)
            ),
            emax_k=jax.lax.dynamic_update_slice(
                ckv.emax_k, ek, (0, 0, cidx)
            ),
            payload_v=jax.lax.dynamic_update_slice(
                ckv.payload_v, pv, (0, 0, cidx, 0)
            ),
            emax_v=jax.lax.dynamic_update_slice(
                ckv.emax_v, ev, (0, 0, cidx)
            ),
            tail_k=jnp.zeros_like(tk),
            tail_v=jnp.zeros_like(tv),
            length=new_len,
        )

    def keep(ckv, tk, tv):
        return ckv._replace(tail_k=tk, tail_v=tv, length=new_len)

    return jax.lax.cond(chunk_full, flush, keep, ckv, tail_k, tail_v)


@functools.partial(jax.jit, static_argnames=("planes", "max_len"))
def compressed_decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    ckv: CompressedKV,
    *,
    planes: int,
    max_len: int,
) -> jax.Array:
    """Attention over (decompressed chunks ++ tail window)."""
    b, _, h, d = q.shape
    kvh = ckv.tail_k.shape[2]
    k_hist = _decode_all(
        ckv.payload_k, ckv.emax_k, planes, max_len, d, ckv.tail_k.dtype
    )
    v_hist = _decode_all(
        ckv.payload_v, ckv.emax_v, planes, max_len, d, ckv.tail_v.dtype
    )
    hist_len = (ckv.length // CHUNK) * CHUNK
    tail_pos = ckv.length - hist_len
    # mask history beyond hist_len, tail beyond tail fill
    k_all = jnp.concatenate([k_hist, ckv.tail_k], axis=1)
    v_all = jnp.concatenate([v_hist, ckv.tail_v], axis=1)
    idx = jnp.arange(max_len + CHUNK)
    valid = (idx < hist_len) | (
        (idx >= max_len) & (idx < max_len + tail_pos)
    )
    # reuse masked decode attention with a validity mask
    qpk = h // kvh
    import numpy as np

    scale = jnp.asarray(1.0 / np.sqrt(d), q.dtype)
    qr = q.reshape(b, kvh, qpk, d) * scale
    logits = jnp.einsum(
        "bgqd,btgd->bgqt", qr, k_all, preferred_element_type=jnp.float32
    )
    logits = jnp.where(valid[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bgqt,btgd->bgqd", p.astype(v_all.dtype), v_all,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, h, d).astype(q.dtype)


def compressed_bytes(ckv: CompressedKV) -> int:
    return int(
        ckv.payload_k.size * 4 + ckv.payload_v.size * 4
        + ckv.emax_k.size * 2 + ckv.emax_v.size * 2
        + ckv.tail_k.size * ckv.tail_k.dtype.itemsize
        + ckv.tail_v.size * ckv.tail_v.dtype.itemsize
    )
