"""Wrapper: fused compressed-history attention + raw-tail merge."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cdecode import kernel
from repro.models.kvcache import CHUNK, CompressedKV


@functools.partial(
    jax.jit, static_argnames=("planes", "max_len")
)
def fused_compressed_decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    ckv: CompressedKV,
    *,
    planes: int,
    max_len: int,
) -> jax.Array:
    b, _, h, d = q.shape
    kvh = ckv.tail_k.shape[2]
    qpk = h // kvh
    scale = jnp.asarray(1.0 / np.sqrt(d), jnp.float32)
    qr = (
        q.reshape(b, kvh, qpk, d).astype(jnp.float32) * scale
    ).reshape(b * kvh, qpk, d)
    hist_len = (ckv.length // CHUNK) * CHUNK
    pk = ckv.payload_k.reshape(b * kvh, -1, ckv.payload_k.shape[-1])
    ek = ckv.emax_k.reshape(b * kvh, -1)
    pv = ckv.payload_v.reshape(b * kvh, -1, ckv.payload_v.shape[-1])
    ev = ckv.emax_v.reshape(b * kvh, -1)
    m_h, l_h, acc_h = kernel.fused_cdecode_attention(
        pk, ek, pv, ev, qr,
        jnp.full((1, 1), hist_len, jnp.int32),
        planes=planes, head_dim=d, qpk=qpk,
    )
    # raw tail window partials
    tail_pos = ckv.length - hist_len
    tk = ckv.tail_k.astype(jnp.float32)  # (B, CHUNK, KVH, D)
    tv = ckv.tail_v.astype(jnp.float32)
    qb = qr.reshape(b, kvh, qpk, d)
    logits = jnp.einsum("bgqd,btgd->bgqt", qb, tk)
    valid = jnp.arange(CHUNK) < tail_pos
    logits = jnp.where(valid[None, None, None], logits, -jnp.inf)
    m_t = logits.max(axis=-1)
    m_t_safe = jnp.where(jnp.isfinite(m_t), m_t, 0.0)
    p = jnp.where(
        valid[None, None, None], jnp.exp(logits - m_t_safe[..., None]),
        0.0,
    )
    l_t = p.sum(axis=-1)
    acc_t = jnp.einsum("bgqt,btgd->bgqd", p, tv)
    # merge the two softmax partial states
    m_h = m_h.reshape(b, kvh, qpk)
    l_h = l_h.reshape(b, kvh, qpk)
    acc_h = acc_h.reshape(b, kvh, qpk, d)
    m = jnp.maximum(m_h, m_t)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    ch = jnp.where(jnp.isfinite(m_h), jnp.exp(m_h - m_safe), 0.0)
    ct = jnp.where(jnp.isfinite(m_t), jnp.exp(m_t - m_safe), 0.0)
    l = l_h * ch + l_t * ct
    acc = acc_h * ch[..., None] + acc_t * ct[..., None]
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return out.reshape(b, 1, h, d).astype(q.dtype)
