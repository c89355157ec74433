"""Jitted public wrappers for the Pallas kernels.

Each op dispatches to the hand-tiled Pallas kernel on TPU and to
``interpret=True`` (Python emulation of the same kernel body) elsewhere, so
the call sites are backend-agnostic.  ``repro.kernels.ref`` holds the
pure-jnp oracles the kernels are validated against.

These ops are the backing store of the ``"pallas"`` compute substrate
(:mod:`repro.core.substrate`): the solver hot loop calls ``fused_dots`` /
``fused_axpy`` / ``spmv_ell`` through the substrate object rather than
inlining jnp, so the same iteration body runs against either the reference
jnp path or these kernels.  ``fused_dots``, ``fused_axpy`` and
``spmv_ell`` all accept both single-RHS ``(n,)`` vectors and multi-RHS
``(n, m)`` blocks: the block variants stream ``(n, m)`` tiles with
per-column coefficients (``fused_axpy`` additionally applies the
per-column convergence mask in-kernel) and amortize the matrix/index
loads of the SpMV over all m columns.  In every case the dot partials are
reduced by the solver's single ``psum``, which is what keeps the
synchronization count at one regardless of m.

``block_jacobi_apply`` backs the block-Jacobi preconditioner of
:mod:`repro.precond` the same way: (n,) and (n, m) applies through the
batched block kernel, with the shared-block (nb == 1) case
short-circuited to one dense matmul.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .flash_attention import flash_attention_pallas
from .fused_axpy import fused_axpy_batched_pallas, fused_axpy_pallas
from .fused_dots import (fused_dots_batched_pallas,
                         fused_dots_health_batched_pallas,
                         fused_dots_health_pallas, fused_dots_pallas)
from .precond_apply import (block_jacobi_apply_batched_pallas,
                            block_jacobi_apply_pallas)
from .spmv_ell import (ell_offsets, spmv_ell_batched_pallas,
                       spmv_ell_pallas)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fused_dots(s, y, r, t, rs) -> jax.Array:
    """9 fused inner products (local partials; reduce with one psum).

    1-D ``(n,)`` inputs -> ``(9,)``; 2-D ``(n, m)`` multi-RHS blocks ->
    ``(9, m)`` (one per-column dot block, still one reduction).
    """
    if s.ndim == 2:
        return fused_dots_batched_pallas(s, y, r, t, rs,
                                         interpret=_interpret())
    return fused_dots_pallas(s, y, r, t, rs, interpret=_interpret())


def fused_dots_health(s, y, r, t, rs, x) -> jax.Array:
    """Guarded fused dots: the 9 solver dots plus 2 in-reduction health
    rows (``x.x`` and a NaN/Inf probe) — see ``ref.fused_dots_health``.

    1-D ``(n,)`` inputs -> ``(11,)``; 2-D ``(n, m)`` blocks -> ``(11, m)``.
    Same single-pass/single-reduction contract as :func:`fused_dots`.
    """
    if s.ndim == 2:
        return fused_dots_health_batched_pallas(s, y, r, t, rs, x,
                                                interpret=_interpret())
    return fused_dots_health_pallas(s, y, r, t, rs, x,
                                    interpret=_interpret())


def spmv_ell(op, x) -> jax.Array:
    """Banded ELL SpMV via the Pallas kernel; falls back to the jnp path
    when the band assumption does not hold.  ``x`` may be an ``(n, m)``
    multi-RHS block — the block kernel reads the matrix tiles once for all
    m columns."""
    from repro.core.linear_operator import ELLOperator
    assert isinstance(op, ELLOperator)
    if not ell_is_banded(op):
        return ref.spmv_ell(op.values, op.cols, x)
    if x.ndim == 2:
        return spmv_ell_batched_pallas(op.values, op.cols, x,
                                       interpret=_interpret())
    return spmv_ell_pallas(op.values, op.cols, x, interpret=_interpret())


#: the Pallas SpMV streams one shifted slice of x per distinct diagonal;
#: ELL matrices with more diagonals than this stay on the jnp gather
MAX_DIAGONALS = 32


def ell_is_banded(op, block_rows: int = 512) -> bool:
    """True when the Pallas SpMV serves ``op``: every nonzero lies within
    ``block_rows`` of the diagonal, on at most :data:`MAX_DIAGONALS`
    distinct diagonals (host-side check on the concrete operator)."""
    offsets = ell_offsets(op.values, op.cols)
    return bool(np.abs(offsets).max() < block_rows
                and offsets.size <= MAX_DIAGONALS)


def block_jacobi_apply(inv_blocks, x) -> jax.Array:
    """Block-Jacobi M^{-1} apply via the Pallas batched block kernel.

    ``inv_blocks``: (nb, bs, bs) pre-inverted diagonal blocks; ``x`` an
    ``(n,)`` vector or ``(n, m)`` multi-RHS block.  The shared-block case
    (nb == 1, every row block identical — constant-coefficient stencils)
    is a single dense matmul that XLA already maps onto the MXU, so it
    short-circuits to the reference path rather than the kernel.
    """
    nb, bs, _ = inv_blocks.shape
    assert x.shape[0] % bs == 0, (x.shape, bs)
    if nb == 1:
        return ref.block_jacobi_apply(inv_blocks, x)
    assert x.shape[0] == nb * bs, (x.shape, inv_blocks.shape)
    if x.ndim == 2:
        return block_jacobi_apply_batched_pallas(inv_blocks, x,
                                                 interpret=_interpret())
    return block_jacobi_apply_pallas(inv_blocks, x, interpret=_interpret())


def fused_axpy(vecs: Dict[str, jax.Array], scalars,
               mask=None) -> Dict[str, jax.Array]:
    """p-BiCGSafe fused vector-update phase (Alg. 3.1 lines 23-32).

    ``(n,)`` vectors dispatch to the single-RHS kernel; ``(n, m)`` blocks
    to the batched kernel with per-column ``(m,)`` coefficients and the
    optional ``(m,)`` convergence ``mask`` applied in-kernel."""
    if vecs["r"].ndim == 2:
        return fused_axpy_batched_pallas(vecs, scalars, mask,
                                         interpret=_interpret())
    assert mask is None, "mask is a multi-RHS (column) concept"
    return fused_axpy_pallas(vecs, scalars, interpret=_interpret())


def flash_attention(qg, k, v, *, scale: float, causal: bool = True
                    ) -> jax.Array:
    """Causal flash attention.  qg: (B,S,K,G,hd), k/v: (B,S,K,hd) (the
    model stack's layout) -> (B,S,K*G*hd)."""
    B, S, K, G, hd = qg.shape
    q = jnp.moveaxis(qg.reshape(B, S, K * G, hd), 1, 2)   # (B,H,S,hd)
    kk = jnp.moveaxis(k, 1, 2)                            # (B,K,S,hd)
    vv = jnp.moveaxis(v, 1, 2)
    o = flash_attention_pallas(q, kk, vv, scale=scale, causal=causal,
                               interpret=_interpret())
    return jnp.moveaxis(o, 2, 1).reshape(B, S, K * G * hd)
