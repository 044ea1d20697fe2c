"""Fused ZFP-decode + flash-decode attention Pallas kernel.

The paper's lesson, applied at the TPU decode boundary: composing
decompress and attend as separate XLA ops *materialises the decoded KV
cache in HBM* and loses more than compression saves (measured in
EXPERIMENTS.md §Perf — the same reason the paper had to modify cuZFP
instead of composing it). This kernel decodes fixed-rate KV chunks
*inside VMEM* and attends to them in the same grid step, so HBM traffic
is the compressed payload only:

  per (batch x kv-head) grid row, per KV chunk:
    payload tile (uint32, VMEM) -> bit-plane unpack -> negabinary ->
    inverse lift -> K tile (CHUNK, D) in VREGs -> partial logits ->
    online-softmax accumulate -> decode V tile -> acc += p V

Outputs are the flash-decoding partial-softmax states (m, l, acc),
merged with the raw tail window by the ops wrapper. Grid:
(B*KVH, n_chunks); the chunk axis revisits the same output block
(standard Pallas accumulation). Validated in interpret mode against the
compositional path (tests/test_cdecode_kernel.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import dispatch
from repro.kernels.zfp import ref as zref
from repro.models.kvcache import CHUNK


def _decode_tile(payload, emax, planes: int, head_dim: int):
    """(nbc, W) uint32 payload -> (CHUNK, D) f32 tile, in-registers."""
    x = zref.decode_blocks(payload.T, emax, planes, 2, jnp.float32).T
    sb, db = CHUNK // 4, head_dim // 4
    x = x.reshape(sb, db, 4, 4).transpose(0, 2, 1, 3)
    return x.reshape(CHUNK, head_dim)


def _kernel(
    pk_ref, ek_ref, pv_ref, ev_ref, q_ref, len_ref,
    m_ref, l_ref, acc_ref, *, planes: int, head_dim: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_tile = _decode_tile(pk_ref[...][0], ek_ref[...][0], planes,
                          head_dim)
    v_tile = _decode_tile(pv_ref[...][0], ev_ref[...][0], planes,
                          head_dim)
    q = q_ref[...][0]  # (QPK, D), already scaled by 1/sqrt(D)
    logits = jnp.einsum(
        "qd,td->qt", q, k_tile, preferred_element_type=jnp.float32
    )
    kpos = ci * CHUNK + jnp.arange(CHUNK)
    valid = kpos < len_ref[...][0, 0]
    logits = jnp.where(valid[None, :], logits, -jnp.inf)
    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1)[None])
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe[0][:, None])
    p = jnp.where(valid[None, :], p, 0.0)
    corr = jnp.where(
        jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0
    )
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + p.sum(axis=-1)[None]
    acc_ref[...] = acc_prev * corr[0][None, :, None] + jnp.einsum(
        "qt,td->qd", p, v_tile, preferred_element_type=jnp.float32
    )[None]


@functools.partial(
    jax.jit,
    static_argnames=("planes", "head_dim", "qpk"),
)
def fused_cdecode_attention(
    payload_k: jax.Array,  # (BG, NB, W) uint32
    emax_k: jax.Array,  # (BG, NB) int32
    payload_v: jax.Array,
    emax_v: jax.Array,
    q_scaled: jax.Array,  # (BG, QPK, D) f32, pre-scaled
    hist_len: jax.Array,  # (1, 1) int32 — compressed tokens valid
    *,
    planes: int,
    head_dim: int,
    qpk: int,
):
    """Returns flash-decoding partials (m, l, acc) over the compressed
    history; the caller merges the raw tail window."""
    bg, nb, w = payload_k.shape
    nbc = (CHUNK // 4) * (head_dim // 4)
    nchunks = nb // nbc
    grid = (bg, nchunks)
    pay_spec = pl.BlockSpec((1, nbc, w), lambda b, c: (b, c, 0))
    em_spec = pl.BlockSpec((1, nbc), lambda b, c: (b, c))
    q_spec = pl.BlockSpec((1, qpk, head_dim), lambda b, c: (b, 0, 0))
    len_spec = pl.BlockSpec((1, 1), lambda b, c: (0, 0))
    out_specs = [
        pl.BlockSpec((1, qpk), lambda b, c: (b, 0)),
        pl.BlockSpec((1, qpk), lambda b, c: (b, 0)),
        pl.BlockSpec((1, qpk, head_dim), lambda b, c: (b, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bg, qpk), jnp.float32),
        jax.ShapeDtypeStruct((bg, qpk), jnp.float32),
        jax.ShapeDtypeStruct((bg, qpk, head_dim), jnp.float32),
    ]
    call = functools.partial(
        pl.pallas_call,
        functools.partial(_kernel, planes=planes, head_dim=head_dim),
        grid=grid,
        in_specs=[pay_spec, em_spec, pay_spec, em_spec, q_spec,
                  len_spec],
        out_specs=out_specs,
        out_shape=out_shape,
    )
    return dispatch(
        lambda *args, interpret: call(interpret=interpret)(*args),
        payload_k, emax_k, payload_v, emax_v, q_scaled, hist_len,
    )
