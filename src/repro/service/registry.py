"""Operator registry: named views onto :mod:`repro.api` solver sessions.

Serving traffic is repetitive: many requests arrive against the same
operator A (same mesh, same physics), often re-constructed per request
by the caller.  Deduplication by *content* — and everything expensive
that rides on it: building the preconditioner ONCE, tracing the
open-loop ``init`` / ``step_chunk`` / ``splice_step`` programs ONCE —
lives in :mod:`repro.api` since PR 5: :func:`repro.api.make_solver`
memoizes whole :class:`~repro.api.LinearSolver` sessions under the
operator-content fingerprint, so the registry here is a thin consumer:
it binds engine-facing *names* (and the engine's chunk size) to
sessions, and two registrations with equal content — in this engine, in
another engine, or via a direct ``repro.make_solver`` call — share one
session and therefore one set of compiled programs.

Each :class:`RegisteredOperator` exposes the session's composed
``M^{-1} ∘ A`` block matvec (operator dispatch intact — a banded ELL
operator on the pallas substrate runs the block-ELL kernel) and the
three jitted open-loop programs sized to the engine's
``(n, max_batch)`` resident block, exactly as before the promotion.
"""
from __future__ import annotations

from typing import Dict, Optional

from repro.api import LinearSolver, make_solver, operator_fingerprint
from repro.core.types import SolverConfig
from repro.precond.base import PrecondLike

from .types import ServiceConfig


class RegisteredOperator:
    """One operator (+ optional preconditioner) bound to the engine block.

    A named, chunk-sized view onto a cached :class:`repro.api
    .LinearSolver` session: the built preconditioner, the composed
    block matvec, and the compiled open-loop programs all belong to the
    session — reusing the session (the api cache's job) is what reuses
    them.
    """

    def __init__(self, name: str, op, precond: PrecondLike,
                 scfg: ServiceConfig, session: LinearSolver):
        self.name = name
        self.op = op
        self.scfg = scfg
        self.session = session
        self.fingerprint = session.fingerprint
        self.sub = session.sub
        #: kernel-backed path assertion: a pallas-substrate service must
        #: actually be running the hand-tiled kernels, not a lookalike
        #: (the session asserts it at construction; surfaced here).
        self.kernel_backed = session.kernel_backed
        self.precond = session.precond          # built ONCE, by the session
        self.bmv = session.block_matvec
        self.n = op.shape[0]
        self.dtype = op.dtype

        # The engine hands these RAW right-hand-side blocks; the left
        # preconditioning of the system (solve M^{-1} A x = M^{-1} b)
        # happens inside the session's jitted programs.  Admission stays
        # fused: splice-then-step is ONE compiled program, so a chunk
        # boundary with refills costs one dispatch + one host read, same
        # as a chunk without.
        chunk = int(scfg.chunk)
        self.init_fn = lambda B, tolv, mitv: session.init(
            B, tol=tolv, maxiter=mitv)
        self.step_fn = lambda st: session.step_chunk(st, chunk)
        self.splice_step_fn = lambda st, mask, Bn, tolv, mitv: \
            session.splice_step(st, mask, Bn, tolv, mitv, chunk)
        self.verify_fn = session.verify

    def __repr__(self):
        pc = getattr(self.precond, "name", None)
        return (f"<RegisteredOperator {self.name!r} n={self.n} "
                f"precond={pc!r} substrate={self.sub.name!r}>")


class OperatorRegistry:
    """Content-addressed operator table (names -> sessions).

    ``register`` is idempotent under re-registration of equal content:
    the same (operator bytes, precond spec) fingerprint returns the
    EXISTING entry — preconditioner and compiled programs included —
    under whichever names it was registered.  The fingerprinting and the
    session reuse are :func:`repro.api.make_solver`'s; this class only
    maps names.
    """

    def __init__(self, scfg: ServiceConfig):
        self._scfg = scfg
        self._by_name: Dict[str, RegisteredOperator] = {}
        self._by_fp: Dict[str, RegisteredOperator] = {}

    def _make_session(self, op, precond: PrecondLike) -> LinearSolver:
        scfg = self._scfg
        cfg = SolverConfig(tol=scfg.tol, maxiter=scfg.maxiter,
                           trace_cap=scfg.trace_cap)
        if scfg.recovery is not None:
            # guarded serving: the open-loop programs step with the
            # (11, m) health reduction and carry typed per-column
            # statuses the engine reads at chunk boundaries
            from repro.resilience.guard import guarded_config
            cfg = guarded_config(cfg, scfg.recovery)
        return make_solver(
            "p-bicgsafe", op, precond=precond, substrate=scfg.substrate,
            config=cfg)

    def register(self, op, precond: PrecondLike = None,
                 name: Optional[str] = None) -> str:
        # fingerprint FIRST, session only on a miss: re-registering known
        # content must stay cheap even when the api layer's LRU has
        # evicted the session (no throwaway preconditioner builds)
        try:
            fp = operator_fingerprint(op, precond)
        except TypeError:
            # the engine needs op.shape/op.dtype for request validation
            # and the service's whole reuse story is content addressing —
            # bare matvec callables support neither
            raise TypeError(
                "the solve service requires a content-addressable operator "
                f"object (got {type(op).__name__}); wrap the matvec in an "
                "operator class (Dense/CSR/ELL/Stencil7) to register it"
            ) from None
        entry = self._by_fp.get(fp)
        if entry is None:
            if name is None:                 # first free auto name
                i = len(self._by_fp)
                while f"op{i}" in self._by_name:
                    i += 1
                name = f"op{i}"
            elif name in self._by_name \
                    and self._by_name[name].fingerprint != fp:
                raise ValueError(
                    f"operator name {name!r} already registered with "
                    "different content")
            # session built only after the name conflict check: a
            # rejected registration must not occupy an api cache slot
            session = self._make_session(op, precond)
            entry = RegisteredOperator(name, op, precond, self._scfg, session)
            self._by_fp[fp] = entry
            self._by_name[name] = entry
        elif name is not None:
            existing = self._by_name.get(name)
            if existing is not None and existing.fingerprint != fp:
                raise ValueError(
                    f"operator name {name!r} already registered with "
                    "different content")
            self._by_name[name] = entry     # alias to the cached entry
        return entry.name if name is None else name

    def register_scenario(self, scenario,
                          name: Optional[str] = None) -> str:
        """Register a scenario's operator + preconditioner by name.

        ``scenario`` is a registered scenario name or a
        :class:`repro.scenarios.Scenario`; the operator is built through
        its plugin (cached per spec content, so two engines registering
        the same scenario share one session).  The engine serves its own
        open-loop p-BiCGSafe blocks under :class:`ServiceConfig` — a
        scenario contributes its operator, precond and name; its
        method/substrate/tol describe the offline sweep cell, not the
        serving configuration.
        """
        from repro.scenarios import resolve_scenario
        sc = resolve_scenario(scenario)
        op = sc.problem()[0]
        return self.register(op, sc.precond, name or sc.name)

    def __getitem__(self, name: str) -> RegisteredOperator:
        try:
            return self._by_name[name]
        except KeyError:
            raise KeyError(
                f"unknown operator {name!r}; registered: "
                f"{sorted(self._by_name)}") from None

    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def entries(self):
        """Unique entries (aliases deduplicated), registration order."""
        return list(self._by_fp.values())

    def names(self):
        return sorted(self._by_name)
