"""The on-chip benchmark of the out-of-core stencil engine (see run.py)."""
