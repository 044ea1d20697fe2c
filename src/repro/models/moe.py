"""Expert-parallel MoE FFN.

Design (DESIGN.md §5): experts are sharded over the ``model`` mesh axis
via ``shard_map``; tokens stay sharded over the data axes. Routing
(small ``(T, E)`` einsum + top-k) runs in regular GSPMD land — so the
load-balancing aux loss is free — and only dispatch/expert-FFN/combine
run inside the shard_map region. Dispatch is argsort-based with a
per-expert capacity, so no ``(T, E, C)`` one-hot tensor is ever
materialised (the GShard/Mesh-TF einsum formulation would dominate both
memory and FLOPs at 128 experts). Each expert shard computes
contributions of *its local experts* for the full local token set and a
single ``psum`` over ``model`` combines them — the same reduction
tensor-parallel FFNs already pay, so expert parallelism adds no extra
collective phase on the baseline path.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, current_rules


def route(x_tokens: jax.Array, router_w: jax.Array, k: int):
    """Top-k routing. x: (T, d) -> (top_w (T,k) f32, top_i (T,k) i32,
    aux_loss scalar)."""
    scores = jax.nn.softmax(
        x_tokens.astype(jnp.float32) @ router_w.astype(jnp.float32), axis=-1
    )
    top_w, top_i = lax.top_k(scores, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = scores.shape[-1]
    hits = jnp.zeros(e).at[top_i.reshape(-1)].add(1.0)
    frac = hits / jnp.maximum(hits.sum(), 1.0)
    prob = scores.mean(0)
    aux = e * jnp.sum(frac * prob)
    return top_w, top_i, aux


def _expert_shard(
    x: jax.Array,  # (T, d) local tokens
    top_w: jax.Array,  # (T, k)
    top_i: jax.Array,  # (T, k)
    wg: jax.Array,  # (E_local, d, f)
    wu: jax.Array,
    wd: jax.Array,  # (E_local, f, d)
    *,
    k: int,
    capacity: int,
    axis: Optional[str],
) -> jax.Array:
    t, d = x.shape
    e_l = wg.shape[0]
    lo = (lax.axis_index(axis) * e_l) if axis else 0
    flat_i = top_i.reshape(-1)
    flat_w = top_w.reshape(-1)
    local = (flat_i >= lo) & (flat_i < lo + e_l)
    le = jnp.where(local, flat_i - lo, e_l)  # e_l == drop bucket
    order = jnp.argsort(le)  # stable: preserves token order per expert
    se = le[order]
    starts = jnp.searchsorted(se, jnp.arange(e_l + 1))
    pos = jnp.arange(se.size) - starts[jnp.clip(se, 0, e_l)]
    keep = (se < e_l) & (pos < capacity)
    slot = jnp.where(keep, se * capacity + pos, e_l * capacity)
    src = order // k
    buf = (
        jnp.zeros((e_l * capacity + 1, d), x.dtype)
        .at[slot]
        .set(jnp.where(keep[:, None], x[src], 0))
    )
    buf = buf[:-1].reshape(e_l, capacity, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wu
    )
    out = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_l * capacity, d)
    out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)], axis=0)
    vals = out[slot] * (flat_w[order] * keep).astype(out.dtype)[:, None]
    y = (
        jnp.zeros((t, d), jnp.float32)
        .at[src]
        .add(vals.astype(jnp.float32))
    )
    if axis:
        y = lax.psum(y, axis)
    return y.astype(x.dtype)


def moe_ffn(
    x: jax.Array,  # (B, S, d)
    router_w: jax.Array,  # (d, E)
    wg: jax.Array,  # (E, d, f)
    wu: jax.Array,
    wd: jax.Array,  # (E, f, d)
    *,
    k: int,
    capacity_factor: float,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,d), aux_loss)."""
    b, s, d = x.shape
    e = router_w.shape[1]
    tokens = x.reshape(b * s, d)
    top_w, top_i, aux = route(tokens, router_w, k)

    mesh, rules = current_mesh(), current_rules()
    axis = rules.get("moe_experts") if rules else None
    if mesh is not None and axis is not None and e % mesh.shape[axis] == 0:
        from repro.distributed.sharding import resolve_spec

        tspec = resolve_spec(
            ("batch", None), tokens.shape, rules, mesh
        )
        dp = tspec[0]
        dp_size = 1
        for a in (dp if isinstance(dp, tuple) else (dp,)):
            if a is not None and a in mesh.shape:
                dp_size *= mesh.shape[a]
        t_local = max(1, (b * s) // dp_size)
        capacity = _capacity(t_local, k, e, capacity_factor)
        fn = functools.partial(
            _expert_shard, k=k, capacity=capacity, axis=axis
        )
        y = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(
                tspec, tspec, tspec,
                P(axis, None, None), P(axis, None, None),
                P(axis, None, None),
            ),
            out_specs=tspec,
            check_vma=False,
        )(tokens, top_w, top_i, wg, wu, wd)
    else:
        capacity = _capacity(b * s, k, e, capacity_factor)
        y = _expert_shard(
            tokens, top_w, top_i, wg, wu, wd,
            k=k, capacity=capacity, axis=None,
        )
    return y.reshape(b, s, d), aux


def _capacity(t_local: int, k: int, e: int, cf: float) -> int:
    """Capacity-factor dispatch at scale; exact (no-drop) dispatch for
    small token counts — decode must never drop a token."""
    cap = int(cf * k * t_local / e)
    if t_local * k <= 4096:
        cap = max(cap, t_local * k)
    return max(1, cap)
