"""Pallas TPU kernels for the perf-critical compute the paper optimizes:
the coalesced (grouped) GEMM superkernel, the coalesced GEMV, and windowed
flash attention. Each has a pure-jnp oracle in ref.py; ops.py holds the
jit'd packing wrappers. Kernels are compiled by Mosaic on a TPU and run in
interpret mode only on a CPU backend (backend.py), where the tests validate
them; tests/test_tpu_compile.py compiles them for a described TPU.
"""
from repro.kernels.coalesced_gemm import coalesced_gemm
from repro.kernels.coalesced_gemv import coalesced_gemv
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import (coalesced_matvec, execute_superkernel,
                               pack_problems, windowed_attention)

__all__ = [
    "coalesced_gemm", "coalesced_gemv", "flash_attention",
    "coalesced_matvec", "execute_superkernel", "pack_problems",
    "windowed_attention",
]
