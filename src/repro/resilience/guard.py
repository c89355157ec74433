"""GuardedSolver: chunked guarded solves with automatic recovery.

The driver that closes the loop between the *device-side* health
monitoring of :mod:`repro.core.multirhs` (``SolverConfig.guard``: the
(9, m) fused reduction widened to (11, m) — same single synchronization
phase, still no dependency edge to the in-flight matvec) and the
*host-side* :class:`~repro.resilience.RecoveryPolicy`:

1. step the guarded state in chunks of ``policy.chunk`` iterations
   through a bound :class:`repro.api.LinearSolver` session,
2. read the (m,) health flags at each chunk boundary (ONE device->host
   transfer, amortized over the chunk),
3. apply the policy: on-trigger residual replacement for drifted
   columns, restart-from-current-x for broken-down / non-finite /
   stagnant columns, substrate degradation (pallas -> jnp, same state
   pytree) after a runtime fault of the compiled kernel program (a
   program the backend refuses to compile is raised, never degraded),
   and per-column method fallback once restarts are exhausted.

Everything the driver does is logged in ``events`` (host-side list of
dicts) and counted in the result state (``replacements`` / ``restarts``
per column), so a recovered solve is auditable.  Clean solves take the
exact unguarded numerical path — the guard rows only *observe* — and pay
only the widened reduction plus one flag read per chunk
(``benchmarks/bench_robustness.py`` pins the overhead).

Construct via ``repro.make_solver(..., recovery=RecoveryPolicy(...))``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import (SolveResult, SolveStatus, SolverConfig,
                              identity_reduce, per_column)
from repro.observe import metrics as _metrics

from .inject import SimulatedKernelFailure
from .policy import RecoveryPolicy
from .recover import replace_columns, restart_columns

#: statuses that restart-from-current-x is allowed to answer
_RESTARTABLE = np.array([SolveStatus.BREAKDOWN.value,
                         SolveStatus.BREAKDOWN_RHO.value,
                         SolveStatus.BREAKDOWN_ALPHA.value,
                         SolveStatus.BREAKDOWN_OMEGA.value,
                         SolveStatus.NONFINITE.value], np.int32)


def _stamp_stagnation(state: dict, mask: jax.Array) -> dict:
    """Freeze columns whose stagnation outlived the restart budget:
    typed STAGNATION, breakdown-frozen so the chunk loop stops burning
    iterations on them."""
    out = dict(state)
    out["breakdown"] = state["breakdown"] | mask
    out["status"] = jnp.where(mask, SolveStatus.STAGNATION.value,
                              state["status"]).astype(jnp.int32)
    return out


class GuardedSolver:
    """A p-BiCGSafe session wrapped with breakdown detection + recovery.

    Duck-types the solve surface of :class:`repro.api.LinearSolver`
    (``solve`` / ``solve_many``); every result carries typed per-column
    :class:`~repro.core.SolveStatus` codes, and ``x`` is guaranteed
    finite (failed columns are sanitized, never NaN).

    Attributes:
      session: the inner guarded session (``config.guard`` is set).
      policy: the bound :class:`RecoveryPolicy`.
      events: host-side audit log — one dict per recovery action
        (replace / restart / substrate_degraded / method_fallback /
        stagnation_giveup), accumulated across solves.
      inject: optional test hook ``(chunk_index, state) -> state`` run
        before each chunk (see :class:`repro.resilience.inject
        .ChunkFaultInjector`); may raise to simulate kernel failures.
    """

    def __init__(self, session, policy: RecoveryPolicy = RecoveryPolicy(),
                 *, inject=None):
        if session.method != "p-bicgsafe":
            raise ValueError(
                "GuardedSolver drives the batched guarded p-BiCGSafe "
                f"iteration (got a {session.method!r} session); "
                "method fallbacks are where other methods come in")
        if not session.config.guard:
            raise ValueError(
                "GuardedSolver needs a guarded session "
                "(SolverConfig.guard=True; make_solver(recovery=...) "
                "sets this up)")
        self.session = session
        self.policy = policy
        self.events: List[Dict[str, Any]] = []
        self.inject = inject
        self._active = session          # degrades to a jnp session on fault
        self._recover_fns: Dict[Any, Any] = {}

    # -- public solve surface ---------------------------------------------

    @property
    def config(self) -> SolverConfig:
        return self.session.config

    def solve(self, b, x0=None, *, tol=None, maxiter=None,
              r0_star=None) -> SolveResult:
        """Guarded single-RHS solve (routed through the m=1 batched
        guarded iteration; scalar-squeezed result)."""
        b = jnp.asarray(b)
        X0 = None if x0 is None else jnp.asarray(x0)[:, None]
        rs = None if r0_star is None else jnp.asarray(r0_star)[:, None]
        res = self.solve_many(b[:, None], X0, tol=tol, maxiter=maxiter,
                              r0_star=rs)
        hist = res.residual_history
        if hist.ndim == 2:
            hist = hist[:, 0]
        trace = res.trace.column(0) if res.trace is not None else None
        return SolveResult(res.x[:, 0], res.iterations[0], res.relres[0],
                           res.converged[0], res.breakdown[0], hist,
                           res.status[0], trace)

    def solve_many(self, B, X0=None, *, tol=None, maxiter=None,
                   r0_star=None) -> SolveResult:
        """Guarded multi-RHS solve with policy-driven recovery.

        The happy path is numerically identical to the unguarded
        ``session.solve_many`` (the health rows read, never write); the
        return differs only in carrying real per-column statuses and in
        surviving faults.
        """
        sess = self.session
        B = sess._as_block(B)
        n, m = B.shape
        cfg = sess.config
        tol_col = np.asarray(per_column(
            cfg.tol if tol is None else tol, m, B.dtype, name="tol"))
        mit_col = np.asarray(per_column(
            cfg.maxiter if maxiter is None else maxiter, m, jnp.int32,
            name="maxiter"))
        state = self._active.init(B, X0, tol=jnp.asarray(tol_col),
                                  maxiter=jnp.asarray(mit_col),
                                  r0_star=r0_star)
        # the (preconditioned) rhs block the recovery programs recompute
        # true residuals against — the state pytree does not carry it
        Bp = self._active._prep(B)

        pol = self.policy
        chunk = pol.chunk
        budget = int(mit_col.max()) if mit_col.size else 0
        # total-work bound: every restart refunds a column's iteration
        # budget, so the chunk loop is capped at (1 + max_restarts)
        # budgets (+1 chunk of slack for boundary effects)
        max_chunks = (1 + pol.max_restarts) * math.ceil(
            max(budget, 1) / chunk) + 1

        # compile outside the fault handler below: a program the backend
        # refuses is a defect to surface, not a runtime fault to degrade
        self._active.compile_step_chunk(state, chunk)
        ci = 0
        degraded_once = False
        while ci < max_chunks:
            try:
                st = state
                if self.inject is not None:
                    st = self.inject(ci, st)
                state = self._active.step_chunk(st, chunk)
            except (SimulatedKernelFailure, RuntimeError) as exc:
                if degraded_once or not self._degrade(exc, ci):
                    raise
                degraded_once = True
                continue            # retry the same chunk, degraded
            ci += 1

            f = jax.device_get({k: state[k] for k in (
                "status", "converged", "breakdown", "iterations",
                "col_maxiter", "drift_flag", "stagnant",
                "replacements", "restarts")})
            active = (~f["converged"] & ~f["breakdown"]
                      & (f["iterations"] < f["col_maxiter"]))

            need_restart = (np.isin(f["status"], _RESTARTABLE)
                            | (f["stagnant"] & active)) \
                & ~f["converged"] \
                & (f["restarts"] < pol.max_restarts)
            need_replace = f["drift_flag"] & active & ~need_restart \
                & (f["replacements"] < pol.max_replacements)
            give_up = f["stagnant"] & active & ~need_restart

            acted = False
            if need_replace.any():
                state = self._recover("replace", replace_columns)(
                    state, jnp.asarray(need_replace), Bp)
                self._log("replace", ci, need_replace)
                acted = True
            if need_restart.any():
                state = self._recover("restart", restart_columns)(
                    state, jnp.asarray(need_restart), Bp)
                self._log("restart", ci, need_restart)
                acted = True
            if give_up.any():
                state = self._stamp(state, jnp.asarray(give_up))
                self._log("stagnation_giveup", ci, give_up)
                active = active & ~give_up
            if not acted and not active.any():
                break

        res = self._active.result(state)
        return self._finalize(res, state, B, tol_col, mit_col)

    # -- internals --------------------------------------------------------

    def _log(self, event: str, chunk: int, mask_or_info) -> None:
        _metrics.RECOVERY_ACTIONS.inc(action=event)
        info = mask_or_info
        if isinstance(info, np.ndarray):
            info = [int(j) for j in np.nonzero(info)[0]]
            self.events.append(dict(event=event, chunk=chunk, columns=info))
        else:
            self.events.append(dict(event=event, chunk=chunk, detail=info))

    def _recover(self, kind: str, fn):
        key = (kind, self._active.sub.name)
        prog = self._recover_fns.get(key)
        if prog is None:
            bmv = self._active.block_matvec
            prog = self._recover_fns[key] = jax.jit(
                lambda state, mask, Bp: fn(bmv, state, mask, Bp))
        return prog

    def _stamp(self, state, mask):
        prog = self._recover_fns.get("stamp")
        if prog is None:
            prog = self._recover_fns["stamp"] = jax.jit(_stamp_stagnation)
        return prog(state, mask)

    def _degrade(self, exc, chunk: int) -> bool:
        """Runtime fault of the compiled step program (compile errors
        surface before the chunk loop): rebuild the step program on the
        jnp substrate and continue from the SAME state pytree (it is a
        plain dict of arrays — substrate-independent by construction)."""
        if not self.policy.substrate_fallback:
            return False
        if getattr(self._active.sub, "name", None) == "jnp" \
                and not isinstance(exc, SimulatedKernelFailure):
            return False                # nothing lower to degrade to
        from repro.api import make_solver
        sess = self.session
        dr = None if sess._dot_reduce is identity_reduce \
            else sess._dot_reduce
        self._active = make_solver(
            sess.method, sess.operator, precond=sess.precond_spec,
            substrate="jnp", config=sess.config, dot_reduce=dr,
            blocked=sess.blocked)
        self._log("substrate_degraded", chunk,
                  dict(error=repr(exc), to="jnp"))
        return True

    def _finalize(self, res: SolveResult, state: dict, B, tol_col,
                  mit_col) -> SolveResult:
        """Method fallback for columns that exhausted recovery, then the
        finite-output guarantee (failed columns never return NaN)."""
        pol = self.policy
        h = jax.device_get(dict(status=res.status, x=res.x,
                                iterations=res.iterations,
                                relres=res.relres, converged=res.converged,
                                breakdown=res.breakdown))
        status = np.asarray(h["status"]).copy()
        failed = np.array([SolveStatus(int(s)).is_failure for s in status])
        x = np.asarray(h["x"]).copy()
        iters = np.asarray(h["iterations"]).copy()
        relres = np.asarray(h["relres"]).copy()
        conv = np.asarray(h["converged"]).copy()
        brk = np.asarray(h["breakdown"]).copy()

        if failed.any() and pol.method_fallback is not None:
            from repro.api import make_solver
            sess = self.session
            fb = make_solver(
                pol.method_fallback, sess.operator,
                precond=sess.precond_spec, substrate="jnp",
                config=dataclasses.replace(
                    sess.config, guard=False, stagnation_window=0,
                    drift_scale=0.0))
            B_host = np.asarray(jax.device_get(B))
            for j in np.nonzero(failed)[0]:
                x0j = x[:, j]
                x0j = x0j if np.isfinite(x0j).all() else None
                r = fb.solve(B_host[:, j], x0j, tol=float(tol_col[j]),
                             maxiter=int(mit_col[j]))
                ok = bool(r.converged)
                self.events.append(dict(
                    event="method_fallback", column=int(j),
                    method=pol.method_fallback,
                    from_status=SolveStatus(int(status[j])).name,
                    converged=ok))
                iters[j] = iters[j] + int(r.iterations)
                if ok:
                    x[:, j] = np.asarray(jax.device_get(r.x))
                    relres[j] = float(r.relres)
                    conv[j] = True
                    brk[j] = False
                    status[j] = SolveStatus.CONVERGED.value

        # finite-output guarantee: whatever went wrong, x never carries
        # NaN/Inf out of the guarded surface
        bad = ~np.isfinite(x)
        if bad.any():
            x = np.where(bad, 0.0, x)
            relres = np.where(np.isfinite(relres), relres, np.inf)
        return SolveResult(jnp.asarray(x), jnp.asarray(iters),
                           jnp.asarray(relres), jnp.asarray(conv),
                           jnp.asarray(brk), res.residual_history,
                           jnp.asarray(status.astype(np.int32)), res.trace)


def guarded_config(config: SolverConfig,
                   policy: RecoveryPolicy) -> SolverConfig:
    """The inner session's config for a given policy: guard on, monitor
    windows forwarded (used by :func:`repro.api.make_solver` and the
    service registry)."""
    return dataclasses.replace(
        config, guard=True, stagnation_window=policy.stagnation_window,
        drift_scale=policy.drift_scale)
