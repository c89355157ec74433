"""Pallas TPU kernels for the perf-critical hot spots.

<name>.py      pl.pallas_call + BlockSpec VMEM tiling
ops.py         jit'd backend-dispatching wrappers (public API)
ref.py         pure-jnp oracles (tests assert allclose against these)

Kernels (each solver kernel has a multi-RHS block variant that streams
(n, m) column tiles — see the *_batched entry points in each module):
  fused_dots       the paper's single fused inner-product phase (9 dots;
                   batched: one (9, m) partial block per pass)
  spmv_ell         banded ELLPACK SpMV, streamed as shifted diagonals
                   (TPU-native layout of the paper's CSR SpMV; batched:
                   diagonal tiles read once for all m columns)
  fused_axpy       p-BiCGSafe's 10 vector updates in one HBM pass
                   (batched: per-column coefficients + the convergence
                   mask applied in-kernel)
  precond_apply    block-Jacobi M^{-1}: batched pre-inverted (bs, bs)
                   block matmuls (backs repro.precond's block_jacobi;
                   batched: block tiles read once for all m columns)
  flash_attention  causal GQA flash attention (model-stack hot spot)
"""
from . import ops, ref

__all__ = ["ops", "ref"]
