"""Batched multi-RHS solves: A X = B for (n, m) right-hand sides.

Krasnopolsky ("Revisiting Performance of BiCGStab Methods for Solving
Systems with Multiple Right-Hand Sides") observes that blocked BiCGStab
variants win not by sharing the Krylov space but by *amortizing memory
traffic and reduction latency* across right-hand sides: every vector phase
streams (n, m) blocks instead of m separate (n,) vectors, and the m
synchronization phases collapse into one.  Applied to the paper's
pipelined single-synchronization methods this is maximal leverage: the
batched p-BiCGSafe iteration below performs ONE ``dot_reduce`` of a
``(9, m)`` partial block per iteration — the same single message as the
m=1 solver, now carrying the inner products of all m systems — and the
fused-dots phase still reads only ``{s, y, r, t_prev, rs}``, preserving
the no-dependency-edge overlap with the in-flight block matvec.

Each column keeps its own coefficients (alpha_j, beta_j, zeta_j, eta_j) —
this is the "individual" blocked mode: convergence per column is
identical to m independent solves in exact arithmetic, and columns that
converge (or break down) early are frozen by masking while the rest
continue.  ``benchmarks/bench_multirhs.py`` measures batched vs. looped.

The whole hot loop routes through the compute substrate
(:mod:`repro.core.substrate`): on ``substrate="pallas"`` the fused
(9, m) dots, the (n, m) update phase (with the convergence mask applied
in-kernel) and the block-ELL SpMV are the hand-tiled kernels, and on the
distributed driver (:func:`repro.core.distributed
.distributed_stencil_solve_batched`) the same iteration runs per shard
with the (9, m) partial block reduced by ONE psum.

Open-loop API (the substrate of :mod:`repro.service`)
-----------------------------------------------------
The iteration is exposed in three jit-friendly pieces so a *continuous
batching* serving layer can keep one resident (n, max_batch) block alive
across heterogeneous requests:

* :func:`init_state`      — build the per-column Krylov state pytree,
* :func:`step_chunk`      — advance ALL columns by up to k iterations with
                            ONE compiled program (early-exits when every
                            column is frozen; still one (9, m) reduction
                            per iteration),
* :func:`splice_columns`  — retire/refill: overwrite a masked subset of
                            columns with fresh right-hand sides and reset
                            per-column Krylov state, mid-flight.  Columns
                            are independent in "individual" blocked mode,
                            so splicing is exact — the surviving columns'
                            trajectories are untouched,
* :func:`verify_columns`  — before a converged column is retired, check
                            its true residual; a column the recurrence
                            fooled is residual-replaced and resumes.

State is per-column throughout: ``tol`` and ``maxiter`` are ``(m,)``
vectors (scalars broadcast via :func:`repro.core.types.per_column`) and
the i=0 coefficient branch keys off each column's OWN iteration count, so
a column spliced into a long-running block starts from its proper first
iteration.  :func:`solve_batched` is the closed-loop wrapper: init + one
chunk of ``config.maxiter`` iterations (behavior-preserving — the
refactor is regression-pinned bitwise in tests/test_substrate_parity.py).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..precond.base import PrecondLike, wrap_block_preconditioned
from ._common import (bicgsafe_breakdown_code, bicgsafe_coefficients,
                      pipelined_recurrence_tail)
from .substrate import SubstrateLike, get_substrate
from .types import (DotReduce, SolveResult, SolveStatus, SolverConfig,
                    identity_reduce, per_column, trace_init, trace_record)

#: Per-column health/monitor fields carried by a GUARDED state pytree
#: (``SolverConfig.guard``); their presence marks a state as guarded.
GUARD_FIELDS = ("status", "drift", "drift_flag", "stall", "best_relres",
                "stagnant", "replacements", "restarts")


def _guard_init(m: int, rdtype, conv0: jax.Array) -> dict:
    """Fresh guard-field values for ``m`` columns (``conv0``: columns that
    are converged at t=0, i.e. zero right-hand sides)."""
    return dict(
        status=jnp.where(conv0, SolveStatus.CONVERGED.value,
                         SolveStatus.RUNNING.value).astype(jnp.int32),
        drift=jnp.zeros((m,), rdtype),
        drift_flag=jnp.zeros((m,), bool),
        stall=jnp.zeros((m,), jnp.int32),
        best_relres=jnp.full((m,), jnp.inf, rdtype),
        stagnant=jnp.zeros((m,), bool),
        replacements=jnp.zeros((m,), jnp.int32),
        restarts=jnp.zeros((m,), jnp.int32))


def _masked(mask_cols, new, old):
    """Per-column select: mask is (m,); operands are (m,) or (n, m).

    ``new`` may arrive with the trailing RHS axis squeezed away — e.g. a
    user ``dot_reduce`` that collapses the degenerate ``(9, 1)`` partial
    block to ``(9,)`` for m=1 turns every coefficient into a scalar.  Such
    lower-rank ``new`` values are broadcast back up to ``old``'s shape
    instead of raising: the state block's shape is authoritative.
    """
    if new.ndim < old.ndim and old.shape[-1] == 1:  # squeezed m=1 only
        new = jnp.broadcast_to(
            new.reshape(new.shape + (1,) * (old.ndim - new.ndim)),
            old.shape)
    elif new.ndim != old.ndim:
        # m>1 stays a loud failure: a dot_reduce that collapses the RHS
        # axis of a real block would otherwise broadcast one column's
        # coefficients to all m
        raise ValueError(
            f"rank mismatch: new {new.shape} vs old {old.shape}")
    m = mask_cols if new.ndim == 1 else mask_cols[None, :]
    return jnp.where(m, new, old)


def batched_matvec(matvec: Callable) -> Callable:
    """Lift a single-vector matvec (n,)->(n,) to (n, m) column blocks."""
    return jax.vmap(matvec, in_axes=1, out_axes=1)


def active_columns(state: dict) -> jax.Array:
    """(m,) bool: columns still iterating (not converged / broken down /
    past their per-column iteration budget)."""
    return ((~state["converged"]) & (~state["breakdown"])
            & (state["iterations"] < state["col_maxiter"]))


def init_state(bmv: Callable,
               B: jax.Array,
               X0: Optional[jax.Array] = None,
               *,
               config: SolverConfig = SolverConfig(),
               r0_star: Optional[jax.Array] = None,
               dot_reduce: DotReduce = identity_reduce,
               substrate: SubstrateLike = "jnp",
               tol=None,
               maxiter=None) -> dict:
    """Build the batched p-BiCGSafe state pytree for ``A X = B``.

    Args:
      bmv: the ``(n, m) -> (n, m)`` block matvec — already lifted (and
        already left-preconditioned, with ``B`` the preconditioned block,
        when preconditioning is in play; :func:`solve_batched` and the
        service registry do this composition).
      B: (n, m) right-hand sides.
      X0: optional (n, m) initial guesses.
      config/r0_star/dot_reduce/substrate: as for :func:`solve_batched`.
      tol: per-column tolerance — scalar or (m,); defaults to
        ``config.tol`` for every column.
      maxiter: per-column iteration budget — scalar or (m,); defaults to
        ``config.maxiter``.  A column stops advancing once its OWN count
        reaches its budget (:func:`active_columns`), which is what lets
        heterogeneous requests share one block.

    Costs one ``dot_reduce`` (the per-column ||r_0||) plus one block
    matvec (S_0 = A R_0; two with a nonzero ``X0``).  The returned dict is
    a pytree of arrays only — it jits, donates, and shards cleanly.
    """
    sub = get_substrate(substrate)
    n, m = B.shape
    X = jnp.zeros_like(B) if X0 is None else X0.astype(B.dtype)
    R0 = B - bmv(X) if X0 is not None else B
    if r0_star is None:
        RS = R0
    else:
        RS = r0_star.astype(B.dtype)
        if RS.ndim == 1:
            RS = jnp.broadcast_to(RS[:, None], B.shape)
    S0 = bmv(R0)                                  # block MV (init): A R_0

    norm_r0 = jnp.sqrt(dot_reduce(sub.dots([(R0, R0)]))[0])   # (m,)
    # Zero right-hand side (or exact initial guess): ||r_0|| == 0 means X
    # already solves that column — mark it converged at t=0 with relres 0
    # instead of letting the body divide by norm_r0 and poison the column
    # with NaN.  Nonzero columns take the same values as before bitwise.
    # (broadcast: a squeezing dot_reduce may return norm_r0 as a scalar
    # for m=1, but the per-column carries must stay (m,))
    conv0 = jnp.broadcast_to(norm_r0 == 0, (m,))
    Z0 = jnp.zeros_like(B)
    ones_m = jnp.ones((m,), B.dtype)
    if config.record_history:
        hist = jnp.full((config.maxiter + 1, m), jnp.nan, norm_r0.dtype)
    else:
        hist = jnp.zeros((0, m), norm_r0.dtype)

    tol_col = per_column(config.tol if tol is None else tol,
                         m, norm_r0.dtype, name="tol")
    maxiter_col = per_column(config.maxiter if maxiter is None else maxiter,
                             m, jnp.int32, name="maxiter")

    st = dict(
        x=X, r=R0, s=S0, p=Z0, u=Z0, t=Z0, y=Z0, z=Z0, w=Z0, l=Z0, g=Z0,
        rs=RS,
        alpha=jnp.zeros((m,), B.dtype), zeta=ones_m, f=ones_m,
        i=jnp.zeros((), jnp.int32),
        iterations=jnp.zeros((m,), jnp.int32),
        relres=jnp.where(conv0, 0.0, 1.0).astype(norm_r0.dtype),
        converged=conv0, breakdown=jnp.zeros((m,), bool),
        norm_r0=norm_r0, tol=tol_col, col_maxiter=maxiter_col,
        hist=hist)
    if config.guard:
        st.update(_guard_init(m, norm_r0.dtype, conv0))
    if config.trace_cap:
        st["trace"] = trace_init(config, norm_r0.dtype, m)
    return st


def splice_columns(bmv: Callable,
                   state: dict,
                   refill: jax.Array,
                   B_new: jax.Array,
                   *,
                   r0_star: Optional[jax.Array] = None,
                   dot_reduce: DotReduce = identity_reduce,
                   substrate: SubstrateLike = "jnp",
                   tol=None,
                   maxiter=None) -> dict:
    """Refill a masked subset of columns with fresh right-hand sides.

    Args:
      bmv: the block matvec the state is being stepped with.
      state: live state pytree from :func:`init_state` / :func:`step_chunk`.
      refill: (m,) bool — True columns are overwritten, False columns are
        carried through bit-untouched (columns are independent in
        "individual" blocked mode, so this is exact, not approximate).
      B_new: (n, m) block holding the fresh right-hand sides in the True
        columns (other columns are ignored).  Fresh columns start from
        x0 = 0.
      r0_star: optional (n,) / (n, m) shadow residual for the fresh
        columns (defaults to their r_0, as in :func:`init_state`).
      tol/maxiter: per-column settings for the fresh columns — scalar or
        (m,) (entries of False columns are ignored).

    Costs one block matvec (A R_0 of the fresh columns, computed on the
    full block so the splice is ONE compiled program for any refill
    count — the frozen columns ride along as zero columns) and one
    ``dot_reduce``.  The global step counter ``i`` (history indexing) is
    preserved; every per-column field of the fresh columns is reset
    exactly as :func:`init_state` builds it.
    """
    m = state["r"].shape[1]
    sub = get_substrate(substrate)
    refill = refill.astype(bool)
    col = refill[None, :]
    B_live = jnp.where(col, B_new.astype(state["r"].dtype), 0.0)
    S0 = bmv(B_live)             # zero columns stay zero: bmv is linear
    norm_new = jnp.sqrt(dot_reduce(sub.dots([(B_live, B_live)]))[0])

    if r0_star is None:
        RS_new = B_live
    else:
        RS_new = r0_star.astype(B_live.dtype)
        if RS_new.ndim == 1:
            RS_new = jnp.broadcast_to(RS_new[:, None], B_live.shape)

    dt = state["r"].dtype
    tol_col = per_column(state["tol"] if tol is None else tol,
                         m, state["tol"].dtype, name="tol")
    maxiter_col = per_column(
        state["col_maxiter"] if maxiter is None else maxiter,
        m, jnp.int32, name="maxiter")

    def vec(new, old):                      # (n, m) fields
        return jnp.where(col, new, old)

    def sca(new, old):                      # (m,) fields
        return jnp.where(refill, new, old)

    zero_m = jnp.zeros((m,), dt)
    out = dict(state)
    out["x"] = vec(jnp.zeros_like(B_live), state["x"])
    out["r"] = vec(B_live, state["r"])
    out["s"] = vec(S0, state["s"])
    out["rs"] = vec(RS_new, state["rs"])
    for k in ("p", "u", "t", "y", "z", "w", "l", "g"):
        out[k] = vec(jnp.zeros_like(B_live), state[k])
    out["alpha"] = sca(zero_m, state["alpha"])
    out["zeta"] = sca(jnp.ones((m,), dt), state["zeta"])
    out["f"] = sca(jnp.ones((m,), dt), state["f"])
    out["iterations"] = sca(jnp.zeros((m,), jnp.int32), state["iterations"])
    # Zero right-hand sides spliced in are converged at t=0 (see
    # init_state) — same guard against the norm_r0 division.
    conv_new = jnp.broadcast_to(norm_new == 0, (m,))
    out["relres"] = sca(jnp.where(conv_new, 0.0, 1.0
                                  ).astype(state["relres"].dtype),
                        state["relres"])
    out["converged"] = sca(conv_new, state["converged"])
    out["breakdown"] = sca(jnp.zeros((m,), bool), state["breakdown"])
    out["norm_r0"] = sca(norm_new, state["norm_r0"])
    out["tol"] = sca(tol_col, state["tol"])
    out["col_maxiter"] = sca(maxiter_col, state["col_maxiter"])
    if state["hist"].shape[0]:
        out["hist"] = jnp.where(col, jnp.nan, state["hist"])
    if "trace" in state:
        # refilled columns start a fresh trajectory: NaN their trace
        # rows (same pattern as hist); the ring keeps recording from
        # the CURRENT global slot, which the harvest layer handles
        out["trace"] = jnp.where(refill[None, None, :], jnp.nan,
                                 state["trace"])
    if "status" in state:                        # guarded state: fresh
        fresh = _guard_init(m, state["norm_r0"].dtype, conv_new)
        for k in GUARD_FIELDS:
            out[k] = sca(fresh[k], state[k])
    return out


def replace_residuals(bmv: Callable, state: dict, mask: jax.Array,
                      R: jax.Array) -> dict:
    """Residual replacement (p-BiCGSafe-rr's Alg. 4.1 reset) of the masked
    columns, given their true residuals ``R = B - A X``.

    The recurred residual and A-images take their definitional values
    (pipelined_bicgsafe Eqns. 3.2/3.7/3.9/3.10):

        r = R,  s = A r,  l = A t,  g = A y,  w = A u

    The primary recurrence vectors ``p, u, t, y, z`` (and ``x``) are exact
    either way and pass through, as do the unmasked columns.  Costs 4
    block matvecs on the full block (ONE compiled program for any mask).
    """
    col = mask.astype(bool)[None, :]
    out = dict(state)
    out["r"] = jnp.where(col, R, state["r"])
    out["s"] = jnp.where(col, bmv(R), state["s"])
    out["l"] = jnp.where(col, bmv(state["t"]), state["l"])
    out["g"] = jnp.where(col, bmv(state["y"]), state["g"])
    out["w"] = jnp.where(col, bmv(state["u"]), state["w"])
    return out


def verify_columns(bmv: Callable, state: dict, B: jax.Array,
                   mask: jax.Array):
    """Check the masked (converged) columns against their true residual.

    The recurred residual of a pipelined iteration drifts from the true
    one ``B - A X`` (most visibly in float32), so a recurrence-converged
    column may be far from its tolerance.  This computes every masked
    column's true relative residual ``||b - A x|| / ||r_0||`` (one block
    matvec); columns at or under their ``tol`` keep their converged flag,
    the rest are un-converged and residual-replaced
    (:func:`replace_residuals`) so that the next :func:`step_chunk`
    resumes them from their true residual.  The replacement runs only
    when some column needs it.

    Returns ``(state, true_relres)``; ``true_relres`` is ``(m,)`` (entries
    of unmasked columns are computed but carry no decision).
    """
    mask = mask.astype(bool)
    R = B.astype(state["r"].dtype) - bmv(state["x"])
    norm = jnp.sqrt(jnp.sum(R * R, axis=0))
    n0 = state["norm_r0"]
    # zero right-hand sides (norm_r0 == 0) converged at t=0 with x = 0
    true = jnp.where(n0 > 0, norm / jnp.where(n0 > 0, n0, 1.0), norm
                     ).astype(state["relres"].dtype)
    redo = mask & ~(true <= state["tol"])      # NaN fails the check too

    def replace(st):
        out = replace_residuals(bmv, st, redo, R)
        out["converged"] = st["converged"] & ~redo
        out["relres"] = jnp.where(redo, true, st["relres"])
        if "status" in st:
            out["status"] = jnp.where(redo, SolveStatus.RUNNING.value,
                                      st["status"])
        return out

    return jax.lax.cond(jnp.any(redo), replace, lambda st: st, state), true


def _make_body(sub, bmv: Callable, config: SolverConfig,
               dot_reduce: DotReduce) -> Callable:
    """One batched p-BiCGSafe iteration: state dict -> state dict.

    Shared verbatim by :func:`solve_batched` and :func:`step_chunk` — the
    single (9, m) reduction, the in-kernel convergence mask, and the
    overlap structure live here and ONLY here.

    With ``config.guard`` the fused phase is the (11, m) health variant
    (same single reduction, same operand independence from the in-flight
    matvec) and the state additionally carries per-column typed status
    codes, a NaN/Inf detector, the Cools drift bound for on-trigger
    residual replacement, and a stagnation counter — everything
    :class:`repro.resilience.GuardedSolver` reads at chunk boundaries.
    Unguarded, the emitted program is bit-for-bit the historical one.
    """
    guard = config.guard

    def body(st):
        r, s, y, t_prev = st["r"], st["s"], st["y"], st["t"]
        RS = st["rs"]
        eps = config.breakdown_threshold(r.dtype)
        active = active_columns(st)                               # (m,)

        # Block MV and the single fused (9, m) reduction — mutually
        # independent, exactly as in the m=1 pipelined iteration.  The
        # guarded (11, m) phase additionally reads the PREVIOUS iterate
        # x (a loop-carried value, no edge to As) for its health rows.
        # (named scopes land in HLO op metadata so the runtime profiler
        # can attribute device time to phases; no ops, bitwise-unchanged.)
        with jax.named_scope("repro.matvec"):
            As = bmv(s)
        with jax.named_scope("repro.reduce"):
            if guard:
                dots = dot_reduce(
                    sub.bicgsafe_dots_health(s, y, r, t_prev, RS, st["x"]))
            else:
                dots = dot_reduce(sub.bicgsafe_dots(s, y, r, t_prev, RS))

        # Each column's i=0 branch keys off its OWN iteration count, so a
        # freshly spliced column in a long-running block initializes its
        # coefficients correctly (for a monolithic solve this is
        # indistinguishable from the global counter).
        beta, alpha, zeta, eta, f, rr, bad = bicgsafe_coefficients(
            dots, st["iterations"], st["alpha"], st["zeta"], st["f"],
            eps)                                                  # (m,)
        relres = jnp.sqrt(jnp.abs(rr)) / st["norm_r0"]
        done = relres <= st["tol"]

        if guard:
            # In-reduction health: rows 9/10 of the fused phase.  A
            # non-finite probe (NaN/Inf anywhere in s/y/t/rs/x) or rr/xx
            # freezes the column exactly like a coefficient breakdown —
            # the poisoned vectors never advance, so NaN cannot spread to
            # the rest of the resident block's history.
            xx, probe = dots[9], dots[10]
            nonfinite = ~(jnp.isfinite(probe) & jnp.isfinite(rr)
                          & jnp.isfinite(xx))
            code = bicgsafe_breakdown_code(
                dots, st["iterations"], st["alpha"], st["zeta"], st["f"],
                eps)
            bad = bad | nonfinite

        # Per-RHS freeze mask: only active-and-unfinished columns advance;
        # converged / broken-down columns stay at their final state.
        advance = active & ~done & ~bad               # (m,)

        # Blocked vector-update phase through the substrate (the (m,)
        # coefficients broadcast over the (n, m) column blocks).  The
        # convergence mask rides into the phase — on the pallas substrate
        # frozen columns write their input tiles back inside the kernel,
        # so no second (n, m) masking pass is needed for these outputs.
        with jax.named_scope("repro.axpy"):
            upd = sub.axpy_phase(
                dict(r=r, p=st["p"], u=st["u"], t=t_prev, y=y, z=st["z"],
                     s=s, l=st["l"], g=st["g"], w=st["w"], x=st["x"],
                     As=As),
                (alpha, beta, zeta, eta), mask=advance)
        p, u, q, w, t = (upd[k] for k in ("p", "u", "q", "w", "t"))
        z, y_next, x_next, r_next = (
            upd[k] for k in ("z", "y", "x", "r"))

        with jax.named_scope("repro.matvec"):
            Aw = bmv(w)                               # block MV #2
        with jax.named_scope("repro.axpy"):
            l, g_next, s_next = pipelined_recurrence_tail(
                q, s, As, st["g"], Aw, alpha, zeta, eta)

        # The recurrence tail (l, g, s) and the scalar carries have no
        # in-kernel mask — freeze them here.
        upd = lambda new, old: _masked(advance, new, old)  # noqa: E731
        relres_out = _masked(active, relres, st["relres"])
        if config.record_history:
            hist_i = st["hist"].at[st["i"]].set(
                jnp.where(active, relres_out.astype(st["hist"].dtype),
                          st["hist"][st["i"]]))
        else:
            hist_i = st["hist"]

        iters_next = jnp.where(advance, st["iterations"] + 1,
                               st["iterations"])
        out = dict(
            x=x_next, r=r_next, s=upd(s_next, s),
            p=p, u=u, t=t, y=y_next, z=z, w=w,
            l=upd(l, st["l"]), g=upd(g_next, st["g"]),
            rs=RS,
            alpha=upd(alpha, st["alpha"]), zeta=upd(zeta, st["zeta"]),
            f=upd(f, st["f"]),
            i=st["i"] + 1,
            iterations=iters_next,
            relres=relres_out,
            converged=st["converged"] | (active & done),
            breakdown=st["breakdown"] | (active & bad & ~done),
            norm_r0=st["norm_r0"], tol=st["tol"],
            col_maxiter=st["col_maxiter"],
            hist=hist_i)

        if guard:
            # Typed per-column status: first terminal event wins; columns
            # that burn their budget are stamped MAXITER as they cross it.
            sts = st["status"]
            sts = jnp.where(active & done,
                            SolveStatus.CONVERGED.value, sts)
            sts = jnp.where(active & ~done & nonfinite,
                            SolveStatus.NONFINITE.value, sts)
            sts = jnp.where(active & ~done & ~nonfinite & bad,
                            jnp.maximum(code, SolveStatus.BREAKDOWN.value),
                            sts)
            sts = jnp.where(advance & (iters_next >= st["col_maxiter"])
                            & (sts == SolveStatus.RUNNING.value),
                            SolveStatus.MAXITER.value, sts)

            # Cools / van-der-Vorst–Ye drift bound: the gap between the
            # recurred and true residual grows like
            # eps * sum_i (||A|| ||x_i|| + ||r_i||); once the bound
            # approaches the ABSOLUTE tolerance tol * ||r_0|| (times
            # drift_scale), the recurred residual can no longer be
            # trusted for the convergence decision and the policy should
            # force a replacement.  ||A|| is estimated in-flight as
            # ||A r||/||r|| = sqrt(a/rr) — row 0 over row 8, free.
            normr = jnp.sqrt(jnp.abs(rr))
            eps_mach = jnp.finfo(r.dtype).eps
            tiny = jnp.finfo(r.dtype).tiny
            normA = jnp.sqrt(jnp.abs(dots[0])
                             / jnp.maximum(jnp.abs(rr), tiny))
            inc = eps_mach * (normA * jnp.sqrt(jnp.abs(xx)) + normr)
            drift = jnp.where(advance, st["drift"] + inc, st["drift"])
            drift_flag = st["drift_flag"] | (
                advance
                & (drift > config.drift_threshold(r.dtype)
                   * st["tol"] * st["norm_r0"]))

            # Stagnation monitor: consecutive iterations without a new
            # best relative residual; sticky flag once the window is hit.
            improved = relres < st["best_relres"]
            best = jnp.where(advance & improved, relres,
                             st["best_relres"])
            stall = jnp.where(advance,
                              jnp.where(improved, 0, st["stall"] + 1),
                              st["stall"])
            if config.stagnation_window > 0:
                stagnant = st["stagnant"] | (
                    stall >= config.stagnation_window)
            else:
                stagnant = st["stagnant"]

            out.update(status=sts, drift=drift, drift_flag=drift_flag,
                       stall=stall, best_relres=best, stagnant=stagnant,
                       replacements=st["replacements"],
                       restarts=st["restarts"])

        if config.trace_cap:
            # Write-only iteration trace: every channel is a value this
            # iteration already computed (the denominators re-express
            # safe_div inputs — XLA CSEs them; the first-iteration
            # omega pivot is ``a``, matching bicgsafe_coefficients).
            # No reduction, no edge to As/Aw — contract-verified.
            a_d, b_d, c_d = dots[0], dots[1], dots[2]
            g_d, h_d = dots[6], dots[7]
            first = st["iterations"] == 0
            if guard:
                drift_ch, status_ch = out["drift"], out["status"]
            else:
                drift_ch = jnp.zeros_like(relres_out)
                status_ch = jnp.where(
                    out["converged"], SolveStatus.CONVERGED.value,
                    jnp.where(out["breakdown"],
                              SolveStatus.BREAKDOWN.value,
                              SolveStatus.RUNNING.value))
            # iteration channel = COMPLETED updates when relres was
            # measured (pre-advance): the terminal detection row keeps
            # the final count and the CONVERGED/BREAKDOWN status.
            out["trace"] = trace_record(st["trace"], st["i"], (
                st["iterations"], relres_out,
                st["zeta"] * st["f"],
                g_d + beta * h_d,
                jnp.where(first, a_d, a_d * b_d - c_d * c_d),
                drift_ch, status_ch))
        return out

    return body


def step_chunk(bmv: Callable,
               state: dict,
               k: int,
               *,
               config: SolverConfig = SolverConfig(),
               dot_reduce: DotReduce = identity_reduce,
               substrate: SubstrateLike = "jnp") -> dict:
    """Advance every live column by up to ``k`` iterations.

    ONE ``lax.while_loop`` — hence one compiled program per (shape, k)
    regardless of which request mix occupies the columns — that exits
    early once every column is frozen (converged, broken down, or past
    its per-column budget).  Each executed iteration performs exactly one
    ``dot_reduce`` of the (9, m) partial block, with no dependency edge
    to the in-flight block matvec (asserted on the engine's step program
    in tests/test_service.py).

    ``k`` must be static under jit (it bounds the loop).  The global
    counter ``state["i"]`` keeps counting across chunks; per-column
    ``iterations`` count from each column's own start (splice resets
    them).
    """
    body = _make_body(get_substrate(substrate), bmv, config, dot_reduce)

    def cond(carry):
        j, st = carry
        return jnp.any(active_columns(st)) & (j < k)

    def step(carry):
        j, st = carry
        return j + 1, body(st)

    _, st = jax.lax.while_loop(cond, step, (jnp.zeros((), jnp.int32), state))
    return st


def result_from_state(state: dict) -> SolveResult:
    """Package a state pytree as the public :class:`SolveResult`.

    ``status``: guarded states carry their typed per-column code through
    the iteration (finalized here: still-RUNNING columns past budget ->
    MAXITER); unguarded states get the coarse classification, with
    still-active columns (open-loop mid-flight packaging) left RUNNING.
    """
    from .types import classify_status
    if "status" in state:
        sts = state["status"]
        running = sts == SolveStatus.RUNNING.value
        sts = jnp.where(running & state["converged"],
                        SolveStatus.CONVERGED.value, sts)
        sts = jnp.where(running & state["breakdown"] & ~state["converged"],
                        SolveStatus.BREAKDOWN.value, sts)
        sts = jnp.where((sts == SolveStatus.RUNNING.value)
                        & (state["iterations"] >= state["col_maxiter"]),
                        SolveStatus.MAXITER.value, sts)
    else:
        sts = jnp.where(
            active_columns(state), SolveStatus.RUNNING.value,
            classify_status(state["converged"], state["breakdown"],
                            state["relres"]))
    trace = None
    if "trace" in state:
        trace = {"buffer": state["trace"], "steps": state["i"]}
    return SolveResult(state["x"], state["iterations"], state["relres"],
                       state["converged"], state["breakdown"],
                       state["hist"], sts.astype(jnp.int32), trace)


def solve_batched(matvec: Callable,
                  B: jax.Array,
                  X0: Optional[jax.Array] = None,
                  *,
                  config: SolverConfig = SolverConfig(),
                  r0_star: Optional[jax.Array] = None,
                  dot_reduce: DotReduce = identity_reduce,
                  substrate: SubstrateLike = "jnp",
                  blocked: bool = False,
                  precond: PrecondLike = None,
                  tol=None) -> SolveResult:
    """Solve A X = B with p-BiCGSafe for all m columns of B at once.

    Args:
      matvec: single-vector matvec (n,) -> (n,); lifted to column blocks
        by the substrate (vmap, or the block-ELL kernel for banded ELL
        operators on the pallas substrate).  May also be an operator
        accepted by the substrate.
      B: (n, m) right-hand sides.
      X0: optional (n, m) initial guesses.
      config/r0_star/dot_reduce/substrate: as for the single-RHS solvers;
        ``r0_star`` is a single (n,) shadow vector shared by all columns
        or an (n, m) block of per-column shadows.
      blocked: the given ``matvec`` already maps (n, m) column blocks to
        (n, m) — used by the distributed driver, whose halo-exchange
        matvec streams whole blocks (one ppermute cascade for all m).
      precond: optional left preconditioner (name or
        :class:`repro.precond.Preconditioner`): the solve runs on
        M^{-1} A with M^{-1} B, every column through the SAME M^{-1}
        (its apply is column-batched, in-kernel for block-Jacobi on the
        pallas substrate), still ONE (9, m) reduction per iteration.
        With ``blocked=True`` pass an instance — name specs need the
        operator object to build from.
      tol: per-column tolerance — scalar or ``(m,)`` vector (heterogeneous
        right-hand sides each converge against their own tolerance);
        defaults to ``config.tol`` broadcast to every column.

    Returns a :class:`SolveResult` with column-batched fields: ``x`` is
    (n, m); ``iterations``, ``relres``, ``converged``, ``breakdown`` are
    (m,); ``residual_history`` is (maxiter+1, m) when recorded.

    One ``dot_reduce`` call per iteration regardless of m (the (9, m)
    partial block is one message), plus one for ||r_0||.  The whole
    per-iteration vector phase — fused dots, update phase, block SpMV —
    runs through the substrate, so ``substrate="pallas"`` executes it on
    the hand-tiled (n, m) kernels with the per-column convergence mask
    applied in-kernel.

    This is the closed-loop wrapper over the open-loop API: one
    :func:`init_state` plus one :func:`step_chunk` of ``config.maxiter``
    iterations (bitwise-equal to the historical monolithic loop —
    regression-pinned in tests/test_substrate_parity.py).
    """
    if B.ndim != 2:
        raise ValueError(f"B must be (n, m); got shape {B.shape}")
    sub = get_substrate(substrate)
    bmv = matvec if blocked else sub.as_block_matvec(matvec)
    bmv, B = wrap_block_preconditioned(sub, bmv, B, precond, matvec)
    state = init_state(bmv, B, X0, config=config, r0_star=r0_star,
                       dot_reduce=dot_reduce, substrate=sub, tol=tol)
    state = step_chunk(bmv, state, config.maxiter, config=config,
                       dot_reduce=dot_reduce, substrate=sub)
    return result_from_state(state)
