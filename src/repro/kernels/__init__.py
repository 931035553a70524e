"""Pallas TPU kernels: Mosaic-compiled on a TPU, run by the Pallas
interpreter on the CPU (tests).  :func:`default_interpret` makes that
choice from the platform for every kernel whose ``interpret`` is None.

Each kernel package ships three layers:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py    — the jit'd public wrapper
  ref.py    — the pure-jnp oracle the tests allclose against
"""
from repro.kernels.platform import default_interpret
from repro.kernels import (conv2d, matmul, flash_attention, sparse_conv,
                           ssm_scan, decode_attention)

__all__ = ["default_interpret", "conv2d", "matmul", "flash_attention",
           "sparse_conv", "ssm_scan", "decode_attention"]
