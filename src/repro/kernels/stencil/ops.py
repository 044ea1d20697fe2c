"""jit'd wrappers for the acoustic stencil kernel.

``backend="ref"`` is the XLA-compiled oracle (ground truth);
``backend="pallas"`` the Pallas kernel: compiled Mosaic on a TPU,
interpret mode on any other platform (``repro.kernels.platform``).
"""

from __future__ import annotations

import functools
from typing import Literal, Tuple

import jax

from . import kernel, ref

Backend = Literal["ref", "pallas"]


@functools.partial(jax.jit, static_argnames=("backend",))
def wave_step(
    p_prev: jax.Array,
    p_cur: jax.Array,
    vel2: jax.Array,
    *,
    backend: Backend = "ref",
) -> Tuple[jax.Array, jax.Array]:
    """One step on padded fields -> (p_next interior, lap interior)."""
    if backend == "pallas":
        return kernel.wave_step_pallas(p_prev, p_cur, vel2)
    return ref.wave_step(p_prev, p_cur, vel2)


@functools.partial(jax.jit, static_argnames=("steps", "backend"))
def temporal_steps(
    p_prev: jax.Array,
    p_cur: jax.Array,
    vel2: jax.Array,
    *,
    steps: int,
    backend: Backend = "ref",
) -> Tuple[jax.Array, jax.Array]:
    """``steps`` fixed-shape time steps on same-shape fields.

    Each step zero-pads by HALO and applies the stencil, so shapes never
    change. Zero padding is the true Dirichlet BC at global volume
    boundaries; at internal out-of-core block boundaries it injects
    garbage that creeps inward at HALO planes/step — the out-of-core
    engine fetches ``steps*HALO`` halo planes so the owned core region
    is exact after ``steps`` steps (the paper's temporal blocking).

    Returns (p_prev, p_cur) after ``steps`` steps.
    """

    def body(carry, _):
        pp, pc = carry
        pn, _ = wave_step(
            ref.pad_bc(pp), ref.pad_bc(pc), vel2, backend=backend,
        )
        return (pc, pn), None

    if backend == "pallas":
        # interpret-mode pallas inside scan is slow; unroll instead
        pp, pc = p_prev, p_cur
        for _ in range(steps):
            (pp, pc), _ = body((pp, pc), None)
        return pp, pc
    (pp, pc), _ = jax.lax.scan(body, (p_prev, p_cur), None, length=steps)
    return pp, pc


def fused_path(backend: Backend, steps: int) -> str:
    """Which stencil path ``fused_temporal_steps`` takes: a name that a
    run can print next to its results."""
    if backend == "pallas":
        launches = -(-steps // kernel.MAX_RUNGS)
        return (f"wave_multistep_pallas: {steps} steps in {launches} "
                f"launch(es) of <= {kernel.MAX_RUNGS} fused rungs")
    return f"ref.wave_step ladder: {steps} XLA steps (lax.scan)"


@functools.partial(jax.jit, static_argnames=("steps", "backend"))
def fused_temporal_steps(
    p_prev: jax.Array,
    p_cur: jax.Array,
    vel2: jax.Array,
    *,
    steps: int,
    backend: Backend = "ref",
) -> Tuple[jax.Array, jax.Array]:
    """Temporal-k entry point: ``steps`` time steps with zero BC.

    The Pallas backend runs ``kernel.wave_multistep_pallas``, which
    tiles any shape and keeps up to ``kernel.MAX_RUNGS`` intermediate
    rungs per launch in VMEM; the ref backend runs the ``temporal_steps``
    ladder. Both compute the per-element expression tree of
    ``ref.ladder_steps`` (tests/test_temporal.py pins this);
    ``fused_path`` names the path taken.
    """
    if backend == "pallas":
        return kernel.wave_multistep_pallas(
            p_prev, p_cur, vel2, steps=steps
        )
    return temporal_steps(p_prev, p_cur, vel2, steps=steps, backend=backend)
