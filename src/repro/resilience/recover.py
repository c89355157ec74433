"""Recovery programs over the batched p-BiCGSafe state pytree.

Both transformations are pure jax functions over the guarded state dict
of :mod:`repro.core.multirhs` — :class:`repro.resilience.GuardedSolver`
jits them once per session and applies them at chunk boundaries to the
columns its policy selects.  Both are masked: untouched columns pass
through bit-identical, so recovery on one column never perturbs its
neighbours (the same exactness argument as ``splice_columns``).

``replace_columns`` is the *on-trigger* generalization of
p-BiCGSafe-rr's Alg. 4.1 reset: identical algebra (recompute ``r`` and
every recurred A-image from true matvecs), but fired by the in-flight
Cools / van-der-Vorst–Ye drift bound (state ``drift_flag``) instead of a
fixed ``rr_epoch`` counter.

``restart_columns`` re-seeds the Krylov space from the current iterate
after a typed breakdown: mathematically a fresh solve of
``A x = b`` with ``x0 = x_current`` (non-finite iterates are sanitized
to 0 first — restarting *from* NaN would be re-poisoning).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.multirhs import _guard_init, replace_residuals
from repro.core.types import SolveStatus


def _vec(mask, new, old):
    return jnp.where(mask[None, :], new, old)


def _sca(mask, new, old):
    return jnp.where(mask, new, old)


def replace_columns(bmv, state: dict, mask: jax.Array,
                    B: jax.Array) -> dict:
    """On-trigger residual replacement of the masked columns.

    The recurred quantities and their definitional invariants
    (pipelined_bicgsafe Eqns. 3.2/3.7/3.9/3.10):

        r = b - A x,  s = A r,  l = A t,  g = A y,  w = A u

    are all recomputed from true matvecs; the primary recurrence vectors
    ``p, u, t, y, z`` (and ``x``) are exact either way and pass through.
    Costs 5 block matvecs on the full block (frozen columns ride along;
    ONE compiled program for any mask).  Resets the masked columns' drift
    bookkeeping and counts the event in ``replacements``.

    ``B`` is the (preconditioned) right-hand-side block the state was
    initialized from — the state itself does not carry it.
    """
    mask = mask.astype(bool)
    r_true = B.astype(state["r"].dtype) - bmv(state["x"])
    out = replace_residuals(bmv, state, mask, r_true)
    rdt = state["drift"].dtype
    m = mask.shape[0]
    out["drift"] = _sca(mask, jnp.zeros((m,), rdt), state["drift"])
    out["drift_flag"] = state["drift_flag"] & ~mask
    out["replacements"] = _sca(mask, state["replacements"] + 1,
                               state["replacements"])
    return out


def restart_columns(bmv, state: dict, mask: jax.Array,
                    B: jax.Array) -> dict:
    """Restart the masked columns from their current iterate.

    Equivalent to a fresh guarded solve of those columns with
    ``x0 = x_current`` (non-finite entries sanitized to 0): true residual
    ``r0 = b - A x0`` becomes both the residual and the fresh shadow
    residual ``r0*``, the auxiliary vectors zero out, the coefficient
    carries reset, and the per-column iteration count restarts (the
    driver bounds *total* work host-side).  ``norm_r0`` is kept from the
    original solve so ``relres`` stays comparable across the restart.
    Columns whose restarted residual is already below tolerance are
    marked converged on the spot.  Counts the event in ``restarts``.
    """
    mask = mask.astype(bool)
    m = mask.shape[0]
    dt = state["r"].dtype
    x_safe = jnp.where(jnp.isfinite(state["x"]), state["x"], 0.0)
    r0 = B.astype(dt) - bmv(x_safe)
    # only the masked columns' r0 matters; keep the rest numerically inert
    r0 = jnp.where(mask[None, :], r0, 0.0)
    s0 = bmv(r0)
    norm_new = jnp.sqrt(jnp.sum(r0 * r0, axis=0))
    relres_new = (norm_new / state["norm_r0"]).astype(state["relres"].dtype)
    conv_new = relres_new <= state["tol"]

    zero_b = jnp.zeros_like(state["r"])
    zero_m = jnp.zeros((m,), dt)
    out = dict(state)
    out["x"] = _vec(mask, x_safe, state["x"])
    out["r"] = _vec(mask, r0, state["r"])
    out["s"] = _vec(mask, s0, state["s"])
    out["rs"] = _vec(mask, r0, state["rs"])
    for k in ("p", "u", "t", "y", "z", "w", "l", "g"):
        out[k] = _vec(mask, zero_b, state[k])
    out["alpha"] = _sca(mask, zero_m, state["alpha"])
    out["zeta"] = _sca(mask, jnp.ones((m,), dt), state["zeta"])
    out["f"] = _sca(mask, jnp.ones((m,), dt), state["f"])
    out["iterations"] = _sca(mask, jnp.zeros((m,), jnp.int32),
                             state["iterations"])
    out["relres"] = _sca(mask, relres_new, state["relres"])
    out["converged"] = _sca(mask, conv_new, state["converged"])
    out["breakdown"] = _sca(mask, jnp.zeros((m,), bool),
                            state["breakdown"])

    # _guard_init stamps CONVERGED where conv_new, RUNNING elsewhere —
    # exactly the restart semantics for status too.
    fresh = _guard_init(m, state["drift"].dtype, conv_new)
    restarts = state["restarts"]
    for k in ("status", "drift", "drift_flag", "stall", "best_relres",
              "stagnant"):
        out[k] = _sca(mask, fresh[k], state[k])
    out["restarts"] = _sca(mask, restarts + 1, restarts)
    return out
