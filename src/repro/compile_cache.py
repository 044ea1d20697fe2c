"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/stencil_outofcore.py``,
``python -m repro.launch.serve --ooc``) call ``place_compile_cache``
once at start-up; importing the library sets nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so nothing
  is set here.
* Otherwise the cache goes to ``.jax_cache/`` at the root of the
  checkout, a fixed path (the path is part of what a later run must
  find again), listed in ``.gitignore``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
