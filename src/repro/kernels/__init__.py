"""Pallas TPU kernels (each: kernel.py + ops.py + ref.py oracle).

  zfp      fixed-rate ZFP-style codec — the paper's compression
  stencil  25-point acoustic wave — the paper's compute
  cdecode  fused ZFP-decode + flash-decode attention (compressed KV)
  sscan    VMEM-resident Mamba-1 selective scan

All validated in interpret mode against their pure-jnp oracles on the
CPU; on a TPU they run as compiled Mosaic (``platform.dispatch``
decides, from the platform a program is lowered for).
"""
