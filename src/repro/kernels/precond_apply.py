"""Pallas TPU kernel: batched block-Jacobi apply.

y_g = B_g @ x_g for every row block g, with B the pre-inverted (bs, bs)
diagonal blocks of the block-Jacobi preconditioner
(:mod:`repro.precond.block_jacobi`).  The apply is a streaming batched
small-matmul: each grid step loads a ``(group, bs, bs)`` tile of inverted
blocks plus the matching ``(group, bs)`` x-tile into VMEM and emits the
``(group, bs)`` product — one HBM pass over the blocks and the vector,
no gather/scatter (contiguous row blocks), no communication.

``block_jacobi_apply_batched_pallas`` is the multi-RHS variant: the
x-tile is ``(group, bs, m)`` and the per-block matmul serves all m
right-hand-side columns from ONE load of the block tile — the same
amortize-the-matrix-stream argument as the block-ELL SpMV kernel.

Layout note: ``bs`` sits on the lane axis, so block sizes below 128 pad
lanes (correct everywhere; bandwidth-optimal for bs >= 128 — use z-line
blocks of a production-sized nz, or fold the group axis, if that matters).
The shared-block case (``inv_blocks`` of shape (1, bs, bs), constant-
coefficient stencils) is NOT routed here: one dense matmul already maps
onto the MXU optimally (see ops.block_jacobi_apply).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _group(nb: int, bs: int) -> int:
    """Blocks per grid step: aim for ~64k elements of block tile, in a
    multiple of 8 (the x tile's sublane axis) unless one step takes all."""
    g = max(1, 65536 // max(bs * bs, 1))
    return min(-(-g // 8) * 8, nb)


def _kernel(blocks_ref, x_ref, y_ref):
    acc = jnp.promote_types(y_ref.dtype, jnp.float32)
    blk = blocks_ref[...].astype(acc)          # (g, bs, bs)
    x = x_ref[...].astype(acc)                 # (g, bs)
    y = jnp.einsum("gij,gj->gi", blk, x)
    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_jacobi_apply_pallas(inv_blocks, x, *, interpret: bool = False
                              ) -> jax.Array:
    """inv_blocks: (nb, bs, bs); x: (n,) with n == nb * bs -> (n,)."""
    nb, bs, _ = inv_blocks.shape
    n = x.shape[0]
    g = _group(nb, bs)
    pad = (-nb) % g
    if pad:   # zero blocks x zero rows -> zero rows, sliced off below
        inv_blocks = jnp.pad(inv_blocks, ((0, pad), (0, 0), (0, 0)))
    xb = jnp.pad(x.reshape(nb, bs), ((0, pad), (0, 0)))
    y = pl.pallas_call(
        _kernel,
        grid=((nb + pad) // g,),
        in_specs=[
            pl.BlockSpec((g, bs, bs), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, bs), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((g, bs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb + pad, bs), x.dtype),
        interpret=interpret,
        name="block_jacobi_apply",
    )(inv_blocks, xb)
    return y[:nb].reshape(n)


def _batched_kernel(blocks_ref, x_ref, y_ref):
    acc = jnp.promote_types(y_ref.dtype, jnp.float32)
    blk = blocks_ref[...].astype(acc)          # (g, bs, bs)
    x = x_ref[...].astype(acc)                 # (g, bs, m)
    y = jnp.einsum("gij,gjm->gim", blk, x)     # block tile read ONCE for m
    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_jacobi_apply_batched_pallas(inv_blocks, x, *,
                                      interpret: bool = False) -> jax.Array:
    """inv_blocks: (nb, bs, bs); x: (n, m) -> (n, m)."""
    nb, bs, _ = inv_blocks.shape
    n, m = x.shape
    g = _group(nb, bs)
    pad = (-nb) % g
    if pad:
        inv_blocks = jnp.pad(inv_blocks, ((0, pad), (0, 0), (0, 0)))
    xb = jnp.pad(x.reshape(nb, bs, m), ((0, pad), (0, 0), (0, 0)))
    y = pl.pallas_call(
        _batched_kernel,
        grid=((nb + pad) // g,),
        in_specs=[
            pl.BlockSpec((g, bs, bs), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, bs, m), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((g, bs, m), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb + pad, bs, m), x.dtype),
        interpret=interpret,
        name="block_jacobi_apply",
    )(inv_blocks, xb)
    return y[:nb].reshape(n, m)
