"""Pallas TPU kernel: the paper's single fused inner-product phase.

Computes the 9 inner products of p-BiCGSafe/ssBiCGSafe2 over the vectors
(s, y, r, t_{i-1}, r0*) in ONE pass: each vector tile is read from HBM into
VMEM exactly once and contributes to all of its dot products, vs. 9
separate dot kernels reading 18 operands.  The local partials this kernel
emits are exactly what the solver's single ``psum`` reduces (Fig. 1.1 of
the paper: local partial sums -> one global reduction).

Layout: every right-hand-side column is laid out as (rows, 128) lane
tiles, so an ``(n, m)`` block becomes ``(m, rows, 128)``; the grid walks
(column, row-block) and accumulates into that column's ``(1, 16)`` output
tile (first 9 entries meaningful).  A single ``(n,)`` vector is the
m = 1 case of the same kernel.  Per-column memory traffic is one pass
whatever m is, and the RHS axis is never padded up to a lane multiple
(which for small m would multiply HBM reads by 128/m).

``fused_dots_batched_pallas`` is the multi-RHS form: (n, m) blocks ->
(9, m) partials.  One HBM pass computes 9*m inner products, and the
solver still reduces the whole block with ONE ``psum``: batching
amortizes both the memory traffic and the reduction latency across
right-hand sides (Krasnopolsky's multi-RHS argument applied to the
pipelined communication model).

``fused_dots_health_pallas`` / ``fused_dots_health_batched_pallas`` are
the guarded variants (repro.resilience): two extra health rows — the
solution-norm dot ``x.x`` and a NaN/Inf finiteness probe — ride along in
the SAME pass and the SAME single reduction, so breakdown/drift
detection costs zero additional communication phases.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
OUT_PAD = 16   # pad 9 -> 16 for clean layout
HEALTH_ROWS = 11  # 9 solver dots + x.x + finiteness probe (still <= OUT_PAD)


def _kernel(*refs):
    *in_refs, out_ref = refs
    acc = out_ref.dtype
    s, y, r, t, rs, *rest = [ref[...].astype(acc) for ref in in_refs]
    rows = [jnp.sum(s * s), jnp.sum(y * y), jnp.sum(s * y), jnp.sum(s * r),
            jnp.sum(y * r), jnp.sum(rs * r), jnp.sum(rs * s),
            jnp.sum(rs * t), jnp.sum(r * r)]
    if rest:                              # guarded: the 2 health rows
        (x,) = rest
        rows += [jnp.sum(x * x), jnp.sum(s + y + t + rs + x)]
    partial = jnp.pad(jnp.stack(rows), (0, OUT_PAD - len(rows)))

    @pl.when(pl.program_id(1) == 0)       # first row block of this column
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial.reshape(1, 1, OUT_PAD)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _dots_columns(*vecs, block_rows: int, interpret: bool) -> jax.Array:
    """(n, m) blocks (5, or 6 for the guarded rows) -> (9 | 11, m)."""
    n, m = vecs[0].shape
    lane_rows = -(-n // LANES)
    rows = -(-lane_rows // block_rows) * block_rows
    padded = rows * LANES

    def prep(v):
        # (n, m) -> (m, rows, LANES): column-major tiles, rows on lanes
        return jnp.pad(v.T, ((0, 0), (0, padded - n))).reshape(
            m, rows, LANES)

    vec_spec = pl.BlockSpec((1, block_rows, LANES), lambda j, i: (j, i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(m, rows // block_rows),
        in_specs=[vec_spec] * len(vecs),
        out_specs=pl.BlockSpec((1, 1, OUT_PAD), lambda j, i: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (m, 1, OUT_PAD), jnp.promote_types(vecs[0].dtype, jnp.float32)),
        interpret=interpret,
        name="fused_dots_health" if len(vecs) == 6 else "fused_dots",
    )(*[prep(v) for v in vecs])
    return out[:, 0, :9 if len(vecs) == 5 else HEALTH_ROWS].T


def fused_dots_pallas(s, y, r, t, rs, *, block_rows: int = 256,
                      interpret: bool = False) -> jax.Array:
    """Returns the 9 fused dots (fp32+).  Inputs: equal-length 1-D vectors."""
    return _dots_columns(*[v[:, None] for v in (s, y, r, t, rs)],
                         block_rows=block_rows, interpret=interpret)[:, 0]


def fused_dots_batched_pallas(s, y, r, t, rs, *, block_rows: int = 256,
                              interpret: bool = False) -> jax.Array:
    """Multi-RHS fused dots: (n, m) inputs -> (9, m) partials (fp32+)."""
    return _dots_columns(s, y, r, t, rs, block_rows=block_rows,
                         interpret=interpret)


def fused_dots_health_pallas(s, y, r, t, rs, x, *, block_rows: int = 256,
                             interpret: bool = False) -> jax.Array:
    """Guarded fused dots: 9 solver dots + 2 health rows (x.x, NaN/Inf
    probe) in one HBM pass — see ``kernels.ref.fused_dots_health`` for
    the row layout.  The padded output still fits the (1, 16) tile, so
    the guarded phase costs one extra VMEM operand and zero extra output
    traffic."""
    return _dots_columns(*[v[:, None] for v in (s, y, r, t, rs, x)],
                         block_rows=block_rows, interpret=interpret)[:, 0]


def fused_dots_health_batched_pallas(s, y, r, t, rs, x, *,
                                     block_rows: int = 256,
                                     interpret: bool = False) -> jax.Array:
    """Multi-RHS guarded dots: (n, m) inputs -> (11, m) partials — the
    guarded solve still issues exactly ONE reduction per iteration."""
    return _dots_columns(s, y, r, t, rs, x, block_rows=block_rows,
                         interpret=interpret)
