"""Pallas TPU kernels: compiled on the TPU, run with an explicit
``interpret=True`` on the CPU backend (see ops.py)."""

from repro.kernels import (dispersed_gemm, flash_attention, ops, ref,
                           rmsnorm, traffic)

__all__ = ["dispersed_gemm", "flash_attention", "ops", "ref",
           "rmsnorm", "traffic"]
