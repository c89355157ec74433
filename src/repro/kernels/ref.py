"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: the solver's f32 contractions: a TPU's default f32 dot rounds operands
#: to bf16, which breaks the Krylov recurrences
HIGHEST = lax.Precision.HIGHEST


def fused_dots(s, y, r, t, rs) -> jax.Array:
    """The 9 inner products of ssBiCGSafe2/p-BiCGSafe's single reduction
    phase: [a,b,c,d,e,f,g,h,rr] (paper Alg. 3.1 lines 7-8)."""
    f32 = jnp.promote_types(s.dtype, jnp.float32)
    return jnp.stack([
        jnp.sum(s * s, dtype=f32), jnp.sum(y * y, dtype=f32),
        jnp.sum(s * y, dtype=f32), jnp.sum(s * r, dtype=f32),
        jnp.sum(y * r, dtype=f32), jnp.sum(rs * r, dtype=f32),
        jnp.sum(rs * s, dtype=f32), jnp.sum(rs * t, dtype=f32),
        jnp.sum(r * r, dtype=f32)])


def fused_dots_batched(s, y, r, t, rs) -> jax.Array:
    """Multi-RHS fused dots: (n, m) inputs -> (9, m) per-column dots."""
    f32 = jnp.promote_types(s.dtype, jnp.float32)
    return jnp.stack([
        jnp.sum(s * s, axis=0, dtype=f32), jnp.sum(y * y, axis=0, dtype=f32),
        jnp.sum(s * y, axis=0, dtype=f32), jnp.sum(s * r, axis=0, dtype=f32),
        jnp.sum(y * r, axis=0, dtype=f32), jnp.sum(rs * r, axis=0, dtype=f32),
        jnp.sum(rs * s, axis=0, dtype=f32), jnp.sum(rs * t, axis=0, dtype=f32),
        jnp.sum(r * r, axis=0, dtype=f32)])


def fused_dots_health(s, y, r, t, rs, x) -> jax.Array:
    """Guarded fused dots: the 9 rows of :func:`fused_dots` plus two
    health rows, all in the SAME single reduction phase (11 rows total):

      row  9: ``x . x``        — solution-norm estimate feeding the
              recurrence-vs-true residual drift bound (Cools criterion);
      row 10: ``sum(s+y+t+rs+x)`` — finiteness probe: NaN/Inf anywhere in
              the operands poisons the sum (``r``'s finiteness is already
              visible through row 8, ``r . r``).

    ``x`` is the PREVIOUS iterate — a loop-carried value, so reading it
    here adds no dependency edge to the in-flight matvec ``A s``.
    """
    f32 = jnp.promote_types(s.dtype, jnp.float32)
    return jnp.concatenate([
        fused_dots(s, y, r, t, rs),
        jnp.stack([jnp.sum(x * x, dtype=f32),
                   jnp.sum(s + y + t + rs + x, dtype=f32)])])


def fused_dots_health_batched(s, y, r, t, rs, x) -> jax.Array:
    """Multi-RHS guarded dots: (n, m) inputs -> (11, m) per-column rows
    (see :func:`fused_dots_health` for the row layout)."""
    f32 = jnp.promote_types(s.dtype, jnp.float32)
    return jnp.concatenate([
        fused_dots_batched(s, y, r, t, rs),
        jnp.stack([jnp.sum(x * x, axis=0, dtype=f32),
                   jnp.sum(s + y + t + rs + x, axis=0, dtype=f32)])])


def spmv_ell(values, cols, x) -> jax.Array:
    """ELLPACK SpMV: y[i] = sum_j values[i,j] * x[cols[i,j]].

    ``x`` may be an (n, m) multi-RHS block: each column is multiplied
    independently (the oracle of the block-ELL kernel).
    """
    if x.ndim == 2:
        return jnp.einsum("rk,rkm->rm", values, x[cols], precision=HIGHEST)
    return jnp.sum(values * x[cols], axis=1)


def fused_axpy(vecs, scalars, mask=None):
    """The fused vector-update phase of p-BiCGSafe (Alg. 3.1 lines 23-32).

    vecs: dict with r,p,u,t,y,z,s,l,g,w,x,As   scalars: (alpha,beta,zeta,eta)
    Returns dict with p,o,u,q,w,t,z,y,x,r (primed values).

    Column-batched: (n, m) blocks with (m,) per-column scalars broadcast.
    ``mask`` (optional (m,) bool, multi-RHS only): frozen columns
    (mask == False) keep their INPUT values for every output that has a
    same-named input (p,u,w,t,z,y,x,r); ``o``/``q`` are always fresh (no
    old state exists — the solver masks their consumers instead).
    """
    al, be, ze, et = scalars
    r, p, u, t, y, z = (vecs[k] for k in "rputyz")
    s, l, g, w, x, As = (vecs[k] for k in ("s", "l", "g", "w", "x", "As"))
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
    out = {"p": p2, "o": o, "u": u2, "q": q, "w": w2, "t": t2,
           "z": z2, "y": y2, "x": x2, "r": r2}
    if mask is not None:
        from .fused_axpy import MASKED_OUT
        mk = mask[None, :] if out["r"].ndim == 2 else mask
        for k in MASKED_OUT:
            out[k] = jnp.where(mk, out[k], vecs[k])
    return out


def block_jacobi_apply(inv_blocks, x) -> jax.Array:
    """Block-Jacobi apply: y_g = inv_blocks[g] @ x_g per row block.

    ``inv_blocks`` is (nb, bs, bs), or (1, bs, bs) for one block shared
    by every row block (constant-coefficient stencils).  ``x`` may be an
    (n,) vector or an (n, m) multi-RHS block; n == (n // bs) * bs.
    """
    nb, bs, _ = inv_blocks.shape
    n = x.shape[0]
    g = n // bs
    if x.ndim == 2:
        xb = x.reshape(g, bs, x.shape[1])
        if nb == 1:
            y = jnp.einsum("ij,gjm->gim", inv_blocks[0], xb,
                           precision=HIGHEST)
        else:
            y = jnp.einsum("gij,gjm->gim", inv_blocks, xb,
                           precision=HIGHEST)
        return y.reshape(x.shape)
    xb = x.reshape(g, bs)
    if nb == 1:
        y = jnp.matmul(xb, inv_blocks[0].T, precision=HIGHEST)
    else:
        y = jnp.einsum("gij,gj->gi", inv_blocks, xb, precision=HIGHEST)
    return y.reshape(n)


def flash_attention(q, k, v, scale: float, causal: bool = True) -> jax.Array:
    """q: (B,H,S,hd)  k/v: (B,K,S,hd), GQA with G=H//K."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    qg = q.reshape(B, K, G, S, hd)
    logits = jnp.einsum("bkgsh,bkth->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        idx = jnp.arange(S)
        mask = idx[:, None] >= idx[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgst,bkth->bkgsh", p, v.astype(jnp.float32))
    return o.reshape(B, H, S, hd).astype(q.dtype)
