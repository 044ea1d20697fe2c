"""Run a check in a child interpreter whose float arithmetic is strict
IEEE-754: no fused multiply-add.

XLA's CPU backend contracts ``a * b + c`` into one FMA where the target
has one, and whether it does depends on how it fused the program. Two
programs that evaluate the same expression tree, such as a Pallas
kernel in interpret mode and its jnp oracle, can then differ in the
last bit. Limiting the child's instruction set to SSE4.2, which has no
FMA, makes both round every operation, so a bitwise comparison tests
the expression trees and not the compiler's fusion choices. The child
runs on the CPU only.
"""

import json
import os
import pathlib
import subprocess
import sys

_SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def run(code: str) -> dict:
    """Execute ``code`` (which prints one JSON object as its last line)
    and return that object."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_cpu_max_isa=SSE4_2",
        PYTHONPATH=str(_SRC),
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
