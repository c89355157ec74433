"""p-BiCGSafe — communication-hiding pipelined BiCGSafe (paper Alg. 3.1)
and p-BiCGSafe-rr — with residual replacement (paper Alg. 4.1).

The paper's core contribution.  Algebraically identical to ssBiCGSafe2 but
with the matvec results replaced by recurrences on auxiliary vectors

    q_i = A s_i + beta_i l_{i-1}              (== A o_i,   Eqn. 3.5)
    w_i = zeta_i q_i + eta_i(g_i + beta_i w_{i-1})   (== A u_i, Eqn. 3.9)
    l_i = q_i - A w_i                         (== A t_i,   Eqn. 3.7)
    g_{i+1} = zeta_i A s_i + eta_i g_i - alpha_i A w_i  (== A y_{i+1}, 3.10)
    s_{i+1} = s_i - alpha_i q_i - g_{i+1}     (== A r_{i+1}, Eqn. 3.2)

so that the single fused inner-product reduction of the iteration consumes
only ``s_i, y_i, r_i, t_{i-1}`` — none of which depend on this iteration's
matvec ``A s_i``.  The reduction and the matvec therefore have **no
dependency edge** and overlap: MPI_Iallreduce+compute in the paper, the XLA
latency-hiding scheduler / dependency-free psum here (DESIGN.md §3;
structural proof in benchmarks/bench_overlap.py).

p-BiCGSafe-rr resets ``r, q, w, l, g, s`` to their true values every
``rr_epoch`` iterations while ``i < rr_maxiter`` (paper §4) to arrest the
round-off drift of the recurred quantities.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..precond.base import PrecondLike, preconditioned_system
from ._common import (bicgsafe_coefficients, init_guess,
                      pipelined_recurrence_tail)
from .substrate import SubstrateLike, get_substrate
from .types import (DotReduce, SolveResult, SolveStatus, SolverConfig,
                    classify_status, history_init, history_update,
                    identity_reduce, trace_init, trace_record)


def _pipelined_solve(matvec, b, x0, config, r0_star, dot_reduce,
                     residual_replacement: bool, substrate: SubstrateLike,
                     precond: PrecondLike = None):
    # Left preconditioning composes M^{-1} INTO the matvec, so every
    # recurred A-image below is an (M^{-1}A)-image and the algebra is
    # unchanged; the M^{-1}-apply becomes part of the in-flight compute
    # the single reduction overlaps (the dots still read none of it).
    sub = get_substrate(substrate)
    matvec, b = preconditioned_system(sub, matvec, b, precond)
    eps = config.breakdown_threshold(b.dtype)
    x = init_guess(b, x0)
    r0 = b - matvec(x) if x0 is not None else b          # MV (init)
    rs = r0 if r0_star is None else r0_star.astype(b.dtype)
    s0 = matvec(r0)                                      # MV (init): s_0 = A r_0

    norm_r0 = jnp.sqrt(dot_reduce(sub.dots([(r0, r0)]))[0])
    # ||r_0|| == 0 (zero rhs, or exact initial guess): x already solves
    # the system — converge at t=0 instead of dividing by zero below.
    conv0 = norm_r0 == 0
    norm_r0 = jnp.where(conv0, jnp.ones_like(norm_r0), norm_r0)
    z0 = jnp.zeros_like(b)
    hist = history_init(config, norm_r0.dtype)

    one = jnp.ones((), b.dtype)
    zero = jnp.zeros((), b.dtype)
    state = dict(
        x=x, r=r0, s=s0, p=z0, u=z0, t=z0, y=z0, z=z0, w=z0, l=z0, g=z0,
        alpha=zero, zeta=one, f=one,
        i=jnp.zeros((), jnp.int32),
        relres=jnp.where(conv0, 0.0, 1.0).astype(norm_r0.dtype),
        converged=conv0, breakdown=jnp.zeros((), bool),
        hist=hist)
    if config.trace_cap:
        state["trace"] = trace_init(config, norm_r0.dtype)
        # rows written (the terminal detection writes one WITHOUT
        # advancing i, so i alone undercounts by one on converge)
        state["trace_steps"] = jnp.zeros((), jnp.int32)

    def cond(st):
        return (~st["converged"]) & (~st["breakdown"]) & (st["i"] < config.maxiter)

    def body(st):
        r, s, y, t_prev = st["r"], st["s"], st["y"], st["t"]

        # MV #1 (A s_i) and the fused reduction are mutually independent:
        # the dots read only {s, y, r, t_prev, rs}.  This is the paper's
        # communication hiding — in the lowered HLO there is no path from
        # the all-reduce to the matvec.  The named scopes land in HLO op
        # metadata so repro.observe.profile can attribute device time to
        # phases; they emit no ops and leave the math bitwise-unchanged.
        with jax.named_scope("repro.matvec"):
            As = matvec(s)
        with jax.named_scope("repro.reduce"):
            dots = dot_reduce(sub.bicgsafe_dots(s, y, r, t_prev, rs))

        beta, alpha, zeta, eta, f, rr, bad = bicgsafe_coefficients(
            dots, st["i"], st["alpha"], st["zeta"], st["f"], eps)
        relres = jnp.sqrt(jnp.abs(rr)) / norm_r0
        done = relres <= config.tol

        # --- blocked vector-update phase (Alg. 3.1 lines 23-32): one
        # substrate call covers all 10 recurrence updates (one fused HBM
        # pass on the pallas substrate).  On the iteration that stops, the
        # result keeps x_i: the one select of the loop, fused into the x
        # update.  Every other vector takes its new value unconditionally
        # (the loop exits and nothing reads them), so each carry is
        # written once an iteration.
        stop = done | bad
        with jax.named_scope("repro.axpy"):
            upd = sub.axpy_phase(
                dict(r=r, p=st["p"], u=st["u"], t=t_prev, y=y, z=st["z"],
                     s=s, l=st["l"], g=st["g"], w=st["w"], x=st["x"], As=As),
                (alpha, beta, zeta, eta))
            x_next = jnp.where(stop, st["x"], upd["x"])
        p, o, u, q, z = (upd[k] for k in ("p", "o", "u", "q", "z"))

        def pipe_tail():
            """Recurrence closure: MV #2 and the three recurred A-images."""
            w = upd["w"]
            with jax.named_scope("repro.matvec"):
                Aw = matvec(w)                        # MV #2 (A w_i)
            with jax.named_scope("repro.axpy"):
                l_n, g_n, s_n = pipelined_recurrence_tail(
                    q, s, As, st["g"], Aw, alpha, zeta, eta)
            return w, upd["t"], upd["y"], upd["r"], l_n, g_n, s_n

        if not residual_replacement:
            w, t, y_next, r_next, l, g_next, s_next = pipe_tail()
        else:
            # Alg. 4.1: every rr_epoch-th step replaces the recurred
            # quantities with true matvec values (p, o, u, z, x keep their
            # recurrence values — they are exact either way, so x stays
            # out of the cond).
            do_rr = ((st["i"] % config.rr_epoch) == 0) & (st["i"] > 0) \
                & (st["i"] < config.rr_maxiter)

            def rr_branch():
                # Alg. 4.1 lines 26-33 + 38-45: w from a true matvec, then
                # reset r, l, g, s to their true values.
                with jax.named_scope("repro.matvec"):
                    w_t = matvec(u)                   # true A u_i
                t_t = o - w_t
                y_t = zeta * s + eta * y - alpha * w_t
                with jax.named_scope("repro.matvec"):
                    r_t = b - matvec(x_next)
                    l_t = matvec(t_t)
                    g_t = matvec(y_t)
                    s_t = matvec(r_t)
                return w_t, t_t, y_t, r_t, l_t, g_t, s_t

            w, t, y_next, r_next, l, g_next, s_next = jax.lax.cond(
                do_rr, rr_branch, pipe_tail)

        new = dict(
            x=x_next, r=r_next, s=s_next, p=p, u=u, t=t, y=y_next, z=z,
            w=w, l=l, g=g_next,
            alpha=alpha, zeta=zeta, f=f,
            i=jnp.where(stop, st["i"], st["i"] + 1), relres=relres,
            converged=done, breakdown=bad & ~done,
            hist=history_update(st["hist"], st["i"], relres, config))
        if config.trace_cap:
            new["trace"] = _trace_row(st, dots, beta, relres, done, bad,
                                      config)
            new["trace_steps"] = st["trace_steps"] + 1
        return new

    st = jax.lax.while_loop(cond, body, state)
    trace = {"buffer": st["trace"], "steps": st["trace_steps"]} \
        if config.trace_cap else None
    return SolveResult(st["x"], st["i"], st["relres"], st["converged"],
                       st["breakdown"], st["hist"],
                       classify_status(st["converged"], st["breakdown"],
                                       st["relres"]), trace)


def _trace_row(st, dots, beta, relres, done, bad, config):
    """Record one single-RHS iteration into the trace ring buffer — all
    channels re-express values the fused phase already computed (XLA
    CSEs the denominators with ``bicgsafe_coefficients``); write-only,
    so the emitted loop math is untouched.  Shared with ssBiCGSafe2.

    The iteration channel is the number of COMPLETED updates when
    relres was measured (the same indexing ``residual_history`` uses):
    the first row is ``(0, 1.0, ...)`` and the terminal row is
    ``(iterations, final relres, ..., CONVERGED/BREAKDOWN)``.
    """
    a_d, b_d, c_d, g_d, h_d = (dots[k] for k in (0, 1, 2, 6, 7))
    first = st["i"] == 0
    status_ch = jnp.where(done, SolveStatus.CONVERGED.value,
                          jnp.where(bad, SolveStatus.BREAKDOWN.value,
                                    SolveStatus.RUNNING.value))
    return trace_record(st["trace"], st["i"], (
        st["i"], relres,
        st["zeta"] * st["f"],
        g_d + beta * h_d,
        jnp.where(first, a_d, a_d * b_d - c_d * c_d),
        jnp.zeros_like(relres), status_ch))


def pbicgsafe_solve(matvec: Callable,
                    b: jax.Array,
                    x0: Optional[jax.Array] = None,
                    *,
                    config: SolverConfig = SolverConfig(),
                    r0_star: Optional[jax.Array] = None,
                    dot_reduce: DotReduce = identity_reduce,
                    substrate: SubstrateLike = "jnp",
                    precond: PrecondLike = None) -> SolveResult:
    """Solve A x = b with p-BiCGSafe (paper Alg. 3.1).

    ``precond`` runs the left-preconditioned system M^{-1} A x = M^{-1} b
    with the M^{-1}-apply scheduled inside the overlap window of the one
    reduction per iteration (relres/tol are in the preconditioned norm).
    """
    return _pipelined_solve(matvec, b, x0, config, r0_star, dot_reduce,
                            residual_replacement=False, substrate=substrate,
                            precond=precond)


def pbicgsafe_rr_solve(matvec: Callable,
                       b: jax.Array,
                       x0: Optional[jax.Array] = None,
                       *,
                       config: SolverConfig = SolverConfig(),
                       r0_star: Optional[jax.Array] = None,
                       dot_reduce: DotReduce = identity_reduce,
                       substrate: SubstrateLike = "jnp",
                       precond: PrecondLike = None) -> SolveResult:
    """Solve A x = b with p-BiCGSafe-rr (paper Alg. 4.1).

    ``config.rr_epoch`` is the paper's ``m`` (default 100, the paper's
    default), ``config.rr_maxiter`` the cutoff ``M``.  ``precond`` as in
    :func:`pbicgsafe_solve`; the replacement branch recomputes the true
    residual of the *preconditioned* system, so the recurred and replaced
    quantities stay consistent.
    """
    return _pipelined_solve(matvec, b, x0, config, r0_star, dot_reduce,
                            residual_replacement=True, substrate=substrate,
                            precond=precond)
