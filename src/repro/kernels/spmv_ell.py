"""Pallas TPU kernel: banded ELLPACK SpMV, streamed as diagonals.

TPU adaptation of the paper's PETSc CSR SpMV (DESIGN.md §3): ELL stores a
fixed ``k`` nonzeros per row as dense (n, k) tiles — a regular layout,
unlike CSR's ragged rows.  A per-row column gather does not lower to
Mosaic (it supports only 2-D gathers inside one vector register), so the
kernel reads the band as diagonals instead: on the host the ELL entries
are folded into ``(nd, n)`` diagonal values, one row per distinct offset
``d = col - row`` (duplicate entries summed, zero padding slots dropped),
and

    y[r] = sum_q dia[q, r] * x[r + offsets[q]]

is a sum of *shifted slices* of x.  Vectors are laid out lane-dense as
(rows, 128); a flat shift by ``d`` is a lane rotation plus a sublane
rotation of the VMEM window, so every load stays tile-aligned.  The
matrix must be banded (|d| < block_rows * 128): for row block i only the
x-blocks i-1, i, i+1 are needed, so x is streamed through VMEM three
blocks at a time (also exactly the halo pattern of the distributed
SpMV).

``spmv_ell_batched_pallas`` is the block (multi-RHS) variant: ``x`` is an
``(n, m)`` column block, and each grid step loads the ``(nd, rows, 128)``
diagonal tile ONCE and applies it to all m columns, where m vmapped 1-D
SpMVs would re-read the matrix m times (Krasnopolsky's amortization
argument applied to the matrix stream).

The diagonal offsets are static kernel parameters, so ``values`` and
``cols`` must be concrete arrays (as they are on a bound operator).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def ell_offsets(values, cols) -> np.ndarray:
    """Sorted distinct diagonal offsets ``col - row`` of the nonzero ELL
    entries (host-side; zero-valued padding slots are not entries)."""
    vals = np.asarray(values)
    off = np.asarray(cols) - np.arange(vals.shape[0])[:, None]
    live = off[vals != 0]
    if live.size == 0:
        return np.zeros(1, np.int64)
    lo = live.min()
    return np.flatnonzero(np.bincount(live - lo)) + lo


def ell_to_dia(values, cols, block_rows: int
               ) -> Tuple[Tuple[int, ...], np.ndarray]:
    """ELL -> (offsets, diagonal values laid out as (nd, rows, 128)).

    ``rows`` is n rounded up to whole (block_rows, 128) tiles; padded rows
    hold zeros.  Raises ValueError when an offset reaches past the one-
    block halo the kernel streams.
    """
    vals = np.asarray(values)
    cols = np.asarray(cols)
    n, k = vals.shape
    offsets = ell_offsets(vals, cols)
    span = block_rows * LANES
    if np.abs(offsets).max() >= span:
        raise ValueError(
            f"ELL band {int(np.abs(offsets).max())} does not fit the "
            f"{span}-element halo of block_rows={block_rows}")
    padded = -(-n // span) * span
    dia = np.zeros((offsets.size, padded), vals.dtype)
    rows = np.arange(n)
    for j in range(k):              # one entry per row per slot: no clashes
        q = np.searchsorted(offsets, cols[:, j] - rows)
        live = vals[:, j] != 0
        dia[q[live], rows[live]] += vals[live, j]
    return (tuple(int(d) for d in offsets),
            dia.reshape(offsets.size, padded // LANES, LANES))


def _window(xcat, d: int, rows: int):
    """Rows [rows, 2*rows) of the (3*rows, 128) window ``xcat``, shifted
    by ``d`` elements in flat (row-major) order — built from rotations so
    the result is an aligned slice."""
    total = xcat.shape[0]
    a, b = divmod(rows * LANES + d, LANES)   # 0 <= a < 2*rows, |d| bounded
    out = xcat
    if b:
        # out[r, l] = xcat[r, l + b] where l + b < 128, else xcat[r+1, ...]
        out = pltpu.roll(out, LANES - b, axis=1)
        nxt = pltpu.roll(out, total - 1, axis=0)
        lane = lax.broadcasted_iota(jnp.int32, out.shape, 1)
        out = jnp.where(lane < LANES - b, out, nxt)
    if a:
        out = pltpu.roll(out, total - a, axis=0)
    return out[:rows]


def _dia_rows(offsets, dia_ref, xprev, xself, xnext, first, last, acc):
    """One (rows, 128) output tile: the sum of the shifted diagonals."""
    rows = xself.shape[0]
    # the clamped halo blocks of the first / last row block are not
    # neighbours: zero them so a non-finite entry there cannot leak
    # through a 0 * x term
    xprev = jnp.where(first, 0, xprev)
    xnext = jnp.where(last, 0, xnext)
    xcat = jnp.concatenate([xprev, xself, xnext], axis=0).astype(acc)
    y = jnp.zeros(xself.shape, acc)
    for q, d in enumerate(offsets):
        y = y + dia_ref[q].astype(acc) * _window(xcat, d, rows)
    return y


def _kernel(offsets, dia_ref, xprev_ref, xself_ref, xnext_ref, y_ref):
    acc = jnp.promote_types(y_ref.dtype, jnp.float32)
    i = pl.program_id(0)           # read outside the loop (interpret mode)
    first, last = i == 0, i == pl.num_programs(0) - 1

    def column(j, carry):          # the diagonal tile serves every column
        y_ref[j] = _dia_rows(offsets, dia_ref, xprev_ref[j], xself_ref[j],
                             xnext_ref[j], first, last,
                             acc).astype(y_ref.dtype)
        return carry

    lax.fori_loop(0, y_ref.shape[0], column, 0)


@functools.partial(jax.jit,
                   static_argnames=("offsets", "block_rows", "interpret"))
def _spmv_dia(dia, x, *, offsets, block_rows, interpret):
    """(nd, rows, 128) diagonals x (n, m) columns -> (n, m)."""
    nd, nrows, _ = dia.shape
    n, m = x.shape
    # (n, m) -> (m, rows, LANES): column-major tiles, rows on lanes
    xt = jnp.pad(x.T, ((0, 0), (0, nrows * LANES - n))).reshape(
        m, nrows, LANES)
    nblk = nrows // block_rows
    x_spec = functools.partial(pl.BlockSpec, (m, block_rows, LANES))
    y = pl.pallas_call(
        functools.partial(_kernel, offsets),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((nd, block_rows, LANES), lambda i: (0, i, 0)),
            x_spec(lambda i: (0, jnp.maximum(i - 1, 0), 0)),     # halo
            x_spec(lambda i: (0, i, 0)),
            x_spec(lambda i: (0, jnp.minimum(i + 1, nblk - 1), 0)),
        ],
        out_specs=pl.BlockSpec((m, block_rows, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, nrows, LANES), x.dtype),
        interpret=interpret,
        name="spmv_ell",
    )(dia, xt, xt, xt)
    return y.reshape(m, -1)[:, :n].T


def spmv_ell_batched_pallas(values, cols, x, *, block_rows: int = 64,
                            interpret: bool = False) -> jax.Array:
    """Block banded ELL SpMV.  values/cols: concrete (n, k); x: (n, m).

    Same band requirement as :func:`spmv_ell_pallas`; each diagonal tile
    is read once per row block and serves all m columns.
    """
    offsets, dia = ell_to_dia(values, cols, block_rows)
    return _spmv_dia(jnp.asarray(dia), x, offsets=offsets,
                     block_rows=block_rows, interpret=interpret)


def spmv_ell_pallas(values, cols, x, *, block_rows: int = 64,
                    interpret: bool = False) -> jax.Array:
    """Banded ELL SpMV.  values/cols: concrete (n, k); x: (n,) — the
    m = 1 case of :func:`spmv_ell_batched_pallas`.

    Requires max|cols[r,:] - r| < block_rows * 128 over the nonzero
    entries (ops.spmv_ell dispatches only banded operators here).
    """
    return spmv_ell_batched_pallas(values, cols, x[:, None],
                                   block_rows=block_rows,
                                   interpret=interpret)[:, 0]
