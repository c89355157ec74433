"""Request/response types and configuration for the solve service.

A :class:`SolveRequest` is one right-hand side against one registered
operator, with its own ``tol`` / ``maxiter`` / ``deadline``; the engine
multiplexes heterogeneous requests onto one resident ``(n, max_batch)``
block (see :mod:`repro.service.engine`) and returns a
:class:`RequestResult` per request, carrying the same solver fields as a
standalone :class:`repro.core.SolveResult` column plus serving telemetry
(queue wait, chunks resident, wall time).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

from repro.core.types import SolveStatus
from repro.resilience.policy import RecoveryPolicy


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Static engine configuration.

    Attributes:
      max_batch: slots in the resident block — the m of the one compiled
        ``(n, m)`` step program.  Request mix never changes it (padding
        unification: empty slots ride along frozen), so there is no shape
        churn and no recompilation under load.
      chunk: iterations per engine step.  Retirement/refill happens only
        at chunk boundaries: larger chunks amortize host round-trips,
        smaller chunks tighten refill latency.  The early-exit inside
        :func:`repro.core.multirhs.step_chunk` means an almost-drained
        block does not burn the full chunk.
      substrate: compute substrate for the hot loop ("jnp" | "pallas" or
        a :class:`repro.core.Substrate` instance) — see
        :mod:`repro.core.substrate`.
      tol / maxiter: per-request defaults when the request leaves them
        unset (``maxiter`` is also the hard per-column budget the step
        program enforces on device).
      recovery: ``None`` runs the engine exactly as before.  A
        :class:`repro.resilience.RecoveryPolicy` turns on guarded
        serving: the resident blocks step with ``SolverConfig.guard``
        (the (11, m) in-reduction health rows — same single reduction
        per iteration), broken columns retire with their typed
        :class:`~repro.core.SolveStatus`, non-finite columns are
        scrubbed before their slot is reused, and failed requests are
        re-enqueued up to ``recovery.max_retries`` times with capped
        exponential backoff.
      trace_cap: per-column iteration-trace ring capacity
        (``SolverConfig.trace_cap``) for the resident blocks.  0 (the
        default) serves untraced; when set, every retirement carries a
        :class:`repro.observe.ConvergenceTrace` on
        ``RequestResult.trace``, harvested at chunk boundaries with the
        ONE host read the engine already does — zero extra
        synchronizations on the device path.
      profile_dir: when set, :meth:`repro.service.SolveEngine.run`
        wraps its drain loop in a :mod:`repro.observe.profile` capture
        window: the device timeline + HLO phase map land under this
        directory, and the per-phase/overlap :class:`~repro.observe
        .profile.ProfileReport` is attached as ``engine.last_profile``
        (and written to ``profile_dir/profile.json``).  Serving
        behavior and results are unchanged; use for one diagnostic run,
        not steady-state serving (the capture holds the whole timeline
        in memory).
    """

    max_batch: int = 8
    chunk: int = 32
    substrate: Any = "jnp"
    tol: float = 1e-8
    maxiter: int = 10_000
    recovery: Optional[RecoveryPolicy] = None
    trace_cap: int = 0
    profile_dir: Optional[str] = None


@dataclasses.dataclass
class SolveRequest:
    """One right-hand side against a registered operator.

    ``tol``/``maxiter`` default from :class:`ServiceConfig`; ``deadline``
    is a wall-clock budget in seconds from submission — a request still
    in flight past its deadline is retired unconverged at the next chunk
    boundary (its partial iterate is returned).

    ``b`` is staged host-side (np) by the engine: it is only consumed
    when the host assembles an admission block, so device puts happen
    once per block, not per request.
    """

    operator: str
    b: np.ndarray
    tol: Optional[float] = None
    maxiter: Optional[int] = None
    deadline: Optional[float] = None
    rid: int = -1
    # host-side bookkeeping (filled by the engine)
    t_submit: float = 0.0
    t_start: Optional[float] = None
    chunks_resident: int = 0
    #: retry attempts consumed so far (guarded serving; see
    #: ``ServiceConfig.recovery``) — the rid is stable across retries
    retries: int = 0
    #: earliest clock time this request may next occupy a slot (retry
    #: backoff; 0.0 = immediately eligible)
    not_before: float = 0.0


@dataclasses.dataclass(frozen=True)
class RequestTelemetry:
    """Serving telemetry for one completed request."""

    queue_wait_s: float       # submit -> first resident in the block
    service_s: float          # first resident -> retirement
    wall_s: float             # submit -> retirement
    chunks_resident: int      # engine chunks the request stayed resident
    deadline_exceeded: bool


@dataclasses.dataclass(frozen=True)
class RequestResult:
    """Per-request outcome: the solver fields a standalone
    ``solve_batched`` column would report, plus telemetry.

    ``status`` is the typed :class:`~repro.core.SolveStatus` of the
    retirement: always filled (guarded serving reports the in-reduction
    per-column code — which BiCGSafe denominator broke, NONFINITE, … —
    unguarded serving the coarse classification; deadline expiry is
    ``DEADLINE`` either way).  ``retries`` counts how many times the
    engine re-ran the request before this outcome (0 without a recovery
    policy).  ``trace`` is the request's per-iteration
    :class:`repro.observe.ConvergenceTrace` when the engine serves with
    ``ServiceConfig.trace_cap`` set (``None`` otherwise).

    A ``converged`` request's ``relres`` is its true relative residual
    ``||M^-1 (b - A x)|| / ||M^-1 b||`` as the engine verified it against
    ``tol`` on the device before retiring it; otherwise ``relres`` is
    the solver's recurred value.
    """

    rid: int
    operator: str
    x: np.ndarray
    iterations: int
    relres: float
    converged: bool
    breakdown: bool
    telemetry: RequestTelemetry
    status: SolveStatus = SolveStatus.CONVERGED
    retries: int = 0
    trace: Optional[Any] = None
