"""Pallas TPU kernel: p-BiCGSafe's fused vector-update phase.

Alg. 3.1 lines 23-32 are 10 vector updates (26 alpha*x + 22 x+y flops per
element, paper Table 3.1).  Issued as separate AXPYs they read/write each
vector several times; this kernel performs the whole phase in a single HBM
pass: 12 tile reads + 10 tile writes per block, all arithmetic in VMEM.
That matters because the phase is pure memory-bound (arith intensity
~0.6 flop/byte) — fusing it is worth ~2.5x on the solver's vector-update
time at the 819 GB/s HBM roofline.

``fused_axpy_batched_pallas`` is the multi-RHS generalization (the
Krasnopolsky regime): the 12 inputs are ``(n, m)`` column blocks, the
coefficients are per-column ``(m,)`` vectors, and the whole phase is still
ONE streaming pass — each ``(block_rows, 128)`` tile of every column is
read once and all 10 updates of that tile are computed in VMEM, so the
memory traffic of the phase is amortized over m right-hand sides.  The
per-column convergence mask is applied *in-kernel*: frozen (converged /
broken-down) columns write back their input tiles unchanged, which is
what lets ``solve_batched`` freeze finished columns without a second
masking pass over the ``(n, m)`` state.  A single ``(n,)`` vector
(``fused_axpy_pallas``) is the m = 1 case of the same kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
IN_ORDER = ("r", "p", "u", "t", "y", "z", "s", "l", "g", "w", "x", "As")
OUT_ORDER = ("p", "o", "u", "q", "w", "t", "z", "y", "x", "r")


# outputs with an input of the same name: their old tile is what a frozen
# column must keep (o and q have no state counterpart — their values for
# frozen columns are discarded by the solver's recurrence-tail masking)
MASKED_OUT = ("p", "u", "w", "t", "z", "y", "x", "r")


def _kernel(scal_ref, r_ref, p_ref, u_ref, t_ref, y_ref, z_ref, s_ref,
            l_ref, g_ref, w_ref, x_ref, As_ref,
            p_o, o_o, u_o, q_o, w_o, t_o, z_o, y_o, x_o, r_o):
    f32 = jnp.promote_types(r_ref.dtype, jnp.float32)
    al = scal_ref[0, 0, 0].astype(f32)     # this column's coefficients
    be = scal_ref[0, 0, 1].astype(f32)
    ze = scal_ref[0, 0, 2].astype(f32)
    et = scal_ref[0, 0, 3].astype(f32)
    mk = scal_ref[0, 0, 4] != 0.0          # convergence mask (1 = advance)
    r = r_ref[...].astype(f32)             # (1, block_rows, LANES) tiles
    p = p_ref[...].astype(f32)
    u = u_ref[...].astype(f32)
    t = t_ref[...].astype(f32)
    y = y_ref[...].astype(f32)
    z = z_ref[...].astype(f32)
    s = s_ref[...].astype(f32)
    l = l_ref[...].astype(f32)
    g = g_ref[...].astype(f32)
    w = w_ref[...].astype(f32)
    x = x_ref[...].astype(f32)
    As = As_ref[...].astype(f32)

    p2 = r + be * (p - u)
    o = s + be * t
    u2 = ze * o + et * (y + be * u)
    q = As + be * l
    w2 = ze * q + et * (g + be * w)
    t2 = o - w2
    z2 = ze * r + et * z - al * u2
    y2 = ze * s + et * y - al * w2
    x2 = x + al * p2 + z2
    r2 = r - al * o - y2

    old = {"p": p, "u": u, "w": w, "t": t, "z": z, "y": y, "x": x, "r": r}
    new = {"p": p2, "o": o, "u": u2, "q": q, "w": w2, "t": t2,
           "z": z2, "y": y2, "x": x2, "r": r2}
    refs = dict(zip(OUT_ORDER,
                    (p_o, o_o, u_o, q_o, w_o, t_o, z_o, y_o, x_o, r_o)))
    for k, ref in refs.items():
        val = jnp.where(mk, new[k], old[k]) if k in old else new[k]
        ref[...] = val.astype(ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_axpy_batched_pallas(vecs: dict, scalars, mask=None, *,
                              block_rows: int = 256,
                              interpret: bool = False) -> dict:
    """Multi-RHS fused update phase: (n, m) blocks, (m,) coefficients.

    ``mask``: optional (m,) bool; columns with ``mask == False`` are frozen
    — every state output (:data:`MASKED_OUT`) writes its input back
    unchanged.  ``o`` and ``q`` are always the fresh values (they have no
    old state; the solver masks their consumers).  Returns the 10 updated
    (n, m) blocks (OUT_ORDER).

    Layout mirrors ``fused_dots_batched_pallas``: each column is tiled as
    (rows, 128) with rows on the lane axis, the grid walks (column,
    row-block), so per-column traffic matches the single-RHS case and
    small m does not force lane padding (an (n, m) minor-dim layout would
    multiply HBM reads by 128/m).  Each column's coefficients and mask
    sit in its own (1, 128) lane tile.  The (n, m) <-> (m, rows, 128)
    relayout at the call boundary is not free — XLA fuses it with the pad
    where it can, but a layout-conscious caller that keeps solver state
    column-major would avoid it entirely (noted as a perf follow-up; the
    kernel body itself is one pass either way).
    """
    n, m = vecs["r"].shape
    dtype = vecs["r"].dtype
    lane_rows = -(-n // LANES)
    rows = -(-lane_rows // block_rows) * block_rows
    padded = rows * LANES

    def prep(v):
        # (n, m) -> (m, rows, LANES): column-major tiles, rows on lanes
        return jnp.pad(v.T, ((0, 0), (0, padded - n))).reshape(
            m, rows, LANES)

    args = [prep(vecs[k]) for k in IN_ORDER]
    sdt = jnp.promote_types(dtype, jnp.float32)
    scal = jnp.zeros((m, 1, LANES), sdt)
    for j, coef in enumerate(scalars):
        scal = scal.at[:, 0, j].set(jnp.asarray(coef, sdt))
    mk = (jnp.ones((m,), sdt) if mask is None
          else jnp.asarray(mask).astype(sdt))
    scal = scal.at[:, 0, 4].set(mk)

    vec_spec = pl.BlockSpec((1, block_rows, LANES), lambda j, i: (j, i, 0))
    outs = pl.pallas_call(
        _kernel,
        grid=(m, rows // block_rows),
        in_specs=[pl.BlockSpec((1, 1, LANES), lambda j, i: (j, 0, 0))]
        + [vec_spec] * 12,
        out_specs=[vec_spec] * 10,
        out_shape=[jax.ShapeDtypeStruct((m, rows, LANES), dtype)] * 10,
        interpret=interpret,
        name="fused_axpy",
    )(scal, *args)
    return {k: o.reshape(m, -1)[:, :n].T
            for k, o in zip(OUT_ORDER, outs)}


def fused_axpy_pallas(vecs: dict, scalars, *, block_rows: int = 256,
                      interpret: bool = False) -> dict:
    """vecs: dict of 12 equal-length vectors (IN_ORDER); scalars: (4,).
    Returns dict of the 10 updated vectors (OUT_ORDER) — the m = 1 case
    of :func:`fused_axpy_batched_pallas`."""
    out = fused_axpy_batched_pallas(
        {k: v[:, None] for k, v in vecs.items()},
        tuple(jnp.reshape(jnp.asarray(c), (1,)) for c in scalars),
        block_rows=block_rows, interpret=interpret)
    return {k: v[:, 0] for k, v in out.items()}
