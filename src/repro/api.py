"""repro.api — the front door: bind-once ``LinearSolver`` sessions.

The paper's value proposition is *per-iteration*: one overlapped fused
reduction hidden behind the in-flight matvec (Huynh & Suito 2021).  The
dominant real workload is *per-operator*: many solves against one fixed
A (Krasnopolsky 2019 makes the same observation for multi-RHS
BiCGStab), re-threading ``substrate=`` / ``precond=`` / ``dot_reduce=``
through a free function on every call — rebuilding the preconditioner
and retracing the whole solver each time.  This module binds the
operator ONCE and amortizes everything else:

    import repro

    solver = repro.make_solver("p-bicgsafe", op, precond="block_jacobi",
                               substrate="pallas")
    x1 = solver.solve(b1)            # traces + compiles once
    x2 = solver.solve(b2)            # reuses the compiled program
    R  = solver.solve_many([b3, b4, b5])   # one (9, m) reduction/iter
    st = solver.init(B); st = solver.step_chunk(st, 32)   # open loop
    d  = solver.on_mesh(mesh)        # distributed binding, same session

    x = repro.solve(op, b)           # one-shot; hits the session cache

One source of truth for caching
-------------------------------
The content-fingerprint machinery that :mod:`repro.service`'s registry
introduced (PR 4) is promoted here: :func:`operator_fingerprint` hashes
the operator pytree (and precond spec) by *content*, and
:func:`make_solver` memoizes whole sessions under that key — so repeat
traffic against an equal-content operator reuses the built
preconditioner AND every compiled program, whether it arrives through
``make_solver``, ``repro.solve``, or the solve service (whose registry
is now a thin consumer of this cache).  Compiled programs inside a
session are memoized per (program kind, derived config, argument
structure); ``jax.jit`` handles shape-keyed retraces below that.

Every binding preserves the two structural invariants the test suite
asserts at the jaxpr level (tests/test_substrate_parity.py, through the
session path too): ONE fused reduction per iteration, with no
dependency edge to the in-flight matvec — single, batched, and
distributed.

The historical free functions (``pbicgsafe_solve`` & co.,
``solve_batched``, the distributed drivers) keep working verbatim as
deprecated shims; sessions delegate to the same underlying
implementations, so results are bitwise-identical program-for-program
(tests/test_api.py).
"""
from __future__ import annotations

import dataclasses
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SOLVERS
from repro.core._deprecation import internal_use
from repro.core.linear_operator import Stencil7Operator
from repro.core.multirhs import (init_state, result_from_state,
                                 splice_columns, step_chunk,
                                 verify_columns)
from repro.core.substrate import SUBSTRATES, SubstrateLike, get_substrate
from repro.core.types import (DotReduce, SolveResult, SolverConfig,
                              identity_reduce, per_column)
from repro.observe import metrics as _metrics
from repro.observe import profile as _profile
from repro.observe.spans import span as _span
from repro.observe.trace import wrap_trace
from repro.precond.base import (PrecondLike, Preconditioner, resolve_precond,
                                validate_precond_spec)

__all__ = [
    "LinearSolver", "DistributedSolver", "make_solver", "solve",
    "operator_fingerprint", "clear_session_cache", "session_cache_info",
]


# ---------------------------------------------------------------------------
# content fingerprinting (promoted from precond/base.py + service/registry.py)
# ---------------------------------------------------------------------------

#: per-object digest memo: id -> (weakref guarding id reuse, digest).
#: Only pytrees whose every leaf is immutable (jax arrays, python
#: scalars, non-writeable ndarrays) are memoized — a live object's
#: content then cannot change, and the weakref callback evicts on death
#: so a recycled id can never alias a dead object's digest.  Operators
#: backed by writeable numpy arrays (mutable in place under the caller's
#: feet) are re-hashed on every call, exactly as before the memo.
_CONTENT_DIGESTS: Dict[int, Tuple[Any, str]] = {}


def _leaf_is_immutable(leaf) -> bool:
    if isinstance(leaf, jax.Array):
        return True
    if isinstance(leaf, np.ndarray):
        return not leaf.flags.writeable
    return isinstance(leaf, (int, float, complex, bool, bytes, str))


def _pytree_is_immutable(obj) -> bool:
    """True when every leaf is immutable — the precondition for BOTH
    content memos (digest and session): a writeable numpy leaf can be
    mutated in place after caching, leaving an entry findable under a
    key its content no longer matches."""
    return all(_leaf_is_immutable(leaf)
               for leaf in jax.tree_util.tree_flatten(obj)[0])


def _content_digest(obj) -> str:
    """sha256 of one pytree's (class, treedef, leaf dtype/shape/bytes).

    Memoized per live immutable-leaved object: repeat fingerprinting of
    the SAME operator (every ``repro.solve`` call in a time-stepping
    loop, every registry re-registration) must not pay a device-to-host
    copy + hash of all leaves just to discover a cache hit.
    """
    import hashlib

    key = id(obj)
    hit = _CONTENT_DIGESTS.get(key)
    if hit is not None and hit[0]() is obj:
        return hit[1]

    h = hashlib.sha256()
    leaves, treedef = jax.tree_util.tree_flatten(obj)
    h.update(type(obj).__name__.encode())
    h.update(repr(treedef).encode())
    for leaf in leaves:
        arr = np.asarray(leaf)
        if arr.dtype == object:
            raise TypeError(
                f"cannot fingerprint non-array content of type "
                f"{type(leaf).__name__} (in {type(obj).__name__}); "
                "content-addressed caching needs operator pytrees "
                "with array leaves")
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    digest = h.hexdigest()
    if not all(_leaf_is_immutable(leaf) for leaf in leaves):
        return digest           # mutable leaves: never memoize
    try:
        ref = weakref.ref(obj, lambda _, k=key: _CONTENT_DIGESTS.pop(k, None))
    except TypeError:
        return digest           # unweakrefable (raw arrays): no memo
    _CONTENT_DIGESTS[key] = (ref, digest)
    return digest


def operator_fingerprint(op, precond: PrecondLike = None) -> str:
    """Content hash identifying an operator (and optionally a precond spec).

    Two operator objects with the same class, static aux data and array
    contents hash identically — this is the cache key under which
    sessions (built preconditioners + compiled solver programs) are
    reused across :func:`make_solver` calls, ``repro.solve`` one-shots,
    and :mod:`repro.service` registrations: repeat traffic against the
    same A must not rebuild block inverses or retrace the step program
    just because the caller re-constructed the operator object.

    ``precond`` folds a name spec or a built
    :class:`~repro.precond.Preconditioner` into the key (a built
    instance hashes by its own pytree contents, so two
    differently-parameterized block-Jacobi instances never collide).

    Raises ``TypeError`` for non-array content (bare matvec callables,
    object-dtype leaves): identity-based hashes would alias after
    garbage collection, so unhashable operators are simply not cached.
    """
    import hashlib

    h = hashlib.sha256()
    h.update(b"op:")
    h.update(_content_digest(op).encode())
    if precond is not None:
        if isinstance(precond, str):
            h.update(f"precond-name:{precond}".encode())
        else:
            h.update(b"precond:")
            h.update(_content_digest(precond).encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the session object
# ---------------------------------------------------------------------------

class LinearSolver:
    """One method bound to one operator: build once, solve many times.

    Construct via :func:`make_solver` (which adds content-keyed session
    caching); the constructor itself resolves the method from
    :data:`repro.core.SOLVERS`, builds the preconditioner ONCE, composes
    the substrate-dispatched (block) matvec, and lazily memoizes one
    jitted program per (program kind, derived config, argument
    structure) in ``self._programs``.

    Attributes:
      method / operator / config: as bound.
      sub: the resolved :class:`~repro.core.Substrate`.
      kernel_backed: True when ``sub`` runs the hand-tiled Pallas kernels.
      precond: the BUILT preconditioner instance (None when unset) —
        validated at bind time, built lazily on first local-solve use;
        ``precond_spec`` keeps the original spec so the distributed
        binding can rebuild shard-locally from a name without paying
        the global build.
      fingerprint: content hash (None when the operator is a bare
        callable — such sessions are never cached).
      stats: ``{"traces", "programs", "solves"}`` — ``traces`` counts
        actual retraces of session programs (the repeat-solve
        amortization metric benchmarks/bench_api.py reports).
    """

    def __init__(self, method: str, operator, *,
                 precond: PrecondLike = None,
                 substrate: SubstrateLike = "jnp",
                 config: SolverConfig = SolverConfig(),
                 dot_reduce: Optional[DotReduce] = None,
                 blocked: bool = False,
                 fingerprint: Optional[str] = None):
        if method not in SOLVERS:
            raise ValueError(f"unknown method {method!r}; expected one of "
                             f"{sorted(SOLVERS)}")
        self.method = method
        self.operator = operator
        self.config = config
        self.sub = get_substrate(substrate)
        self.kernel_backed = bool(getattr(self.sub, "kernel_backed", False))
        if getattr(self.sub, "name", None) == "pallas":
            assert self.kernel_backed, (
                "substrate resolved to 'pallas' but is not kernel-backed")
        if not blocked:
            self.sub.check_dtype(getattr(operator, "dtype", None))
        self.blocked = bool(blocked)
        self.precond_spec = precond
        self.fingerprint = fingerprint
        self._dot_reduce = identity_reduce if dot_reduce is None else dot_reduce
        self.stats: Dict[str, int] = {"traces": 0, "programs": 0, "solves": 0}
        self._programs: Dict[Any, Callable] = {}
        self._mesh_bindings: Dict[Any, "DistributedSolver"] = {}
        #: ProfileReport of the most recent ``solve(..., profile=dir)``
        self.last_profile = None

        # spec validated EAGERLY (bad binds fail at make_solver time) but
        # built LAZILY on first local-solve use: a session only ever used
        # via .on_mesh rebuilds the preconditioner shard-locally and must
        # not pay the global build (e.g. block-Jacobi's dense inversions)
        validate_precond_spec(precond, operator)
        self._precond_built = False
        self._precond_val: Optional[Preconditioner] = None
        self._bmv: Optional[Callable] = None
        self._papply_val: Optional[Callable] = None

    @property
    def precond(self) -> Optional[Preconditioner]:
        """The BUILT preconditioner (first access builds it, once).

        The build runs under ``ensure_compile_time_eval``: the first
        access often happens while tracing a session program, and the
        built arrays are cached on the session — they must be concrete
        constants, not tracers of whichever trace got there first.
        """
        if not self._precond_built:
            with _span("api.precond_build",
                       spec=str(self.precond_spec)), \
                    jax.ensure_compile_time_eval():
                self._precond_val = resolve_precond(self.precond_spec,
                                                    self.operator)
            self._precond_built = True
        return self._precond_val

    @property
    def _papply(self) -> Optional[Callable]:
        if self._bmv is None:
            self.block_matvec       # composition builds _papply_val
        return self._papply_val

    @property
    def block_matvec(self) -> Callable:
        """Substrate-dispatched block matvec, composed ONCE with M^{-1}.

        Left preconditioning INSIDE the matvec keeps operator dispatch
        to the Pallas kernels and the overlap window — see
        repro/precond/base.py.
        """
        if self._bmv is None:
            raw_bmv = self.operator if self.blocked \
                else self.sub.as_block_matvec(self.operator)
            pc = self.precond
            if pc is None:
                self._bmv = raw_bmv
            else:
                papply = self.sub.as_precond_apply(pc)
                self._papply_val = papply
                self._bmv = lambda X: papply(raw_bmv(X))
        return self._bmv

    def __repr__(self):
        # precond_spec, not the precond property: repr (debugger, log
        # line) must never trigger the lazy global build
        pc = self.precond_spec if not self._precond_built else \
            getattr(self._precond_val, "name", None)
        fp = (self.fingerprint or "uncached")[:12]
        return (f"<LinearSolver {self.method!r} substrate={self.sub.name!r} "
                f"precond={pc!r} fingerprint={fp!r}>")

    def verify_contracts(self, *, bindings: Optional[Sequence[str]] = None,
                         mesh=None, m: int = 3,
                         contracts: Optional[Sequence[str]] = None,
                         raise_on_violation: bool = False):
        """Statically verify the paper's communication contracts on THIS
        session's bindings — tracing only, no solve runs.

        Traces the session's method/operator/substrate/precond/guard
        through :mod:`repro.analysis` and runs the contract passes
        (one fused reduction per iteration, overlap-edge freedom, kernel
        backing, dtype flow; plus the single-psum pass when ``mesh=`` is
        given and the operator is a stencil).

        Args:
          bindings: binding kinds to trace; default: ``["batched"]`` for
            p-BiCGSafe sessions (the multi-RHS front door), else
            ``["single"]``.
          mesh: a ``jax.sharding.Mesh`` — adds the sharded ``"mesh"``
            cell to the sweep.
          contracts: names from :data:`repro.analysis.PASSES` to run
            (default: all applicable).
          raise_on_violation: raise ``ValueError`` listing the violated
            contracts instead of returning reports that carry them.

        Returns:
          list of :class:`repro.analysis.ContractReport`, one per traced
          binding.
        """
        from repro.analysis import run_passes, trace_binding
        if bindings is None:
            bindings = ["batched"] if (self.method == "p-bicgsafe"
                                       or self.blocked) else ["single"]
        bindings = list(bindings)
        if mesh is not None and "mesh" not in bindings:
            bindings.append("mesh")
        reports = []
        for binding in bindings:
            reports.append(run_passes(trace_binding(
                self.method, self.operator, binding=binding,
                substrate=self.sub, precond=self.precond,
                guard=self.config.guard, m=m, config=self.config,
                mesh=mesh if binding == "mesh" else None,
                blocked=self.blocked), names=contracts))
        if raise_on_violation:
            bad = [(r.spec.label, f) for r in reports
                   for f in r.violations]
            if bad:
                raise ValueError(
                    "contract violation(s) on this session's bindings:\n"
                    + "\n".join(f"  {label}: {f.contract} — {f.detail}"
                                for label, f in bad))
        return reports

    def _require_pbicgsafe(self, what: str) -> None:
        """The batched/open-loop iteration (repro.core.multirhs) IS
        p-BiCGSafe; a session bound to another method must not silently
        run the wrong algorithm through these entry points."""
        if self.method != "p-bicgsafe":
            raise ValueError(
                f"{what} runs the batched p-BiCGSafe iteration only "
                f"(this session is bound to {self.method!r}); bind a "
                '"p-bicgsafe" session for multi-RHS / open-loop solves, '
                "or use .solve per right-hand side")

    # -- program memoization ----------------------------------------------

    def _program(self, key, build: Callable[[], Callable]) -> Callable:
        fn = self._programs.get(key)
        if fn is None:
            with _span("api.program_build", method=self.method,
                       kind=str(key[0]) if key else ""):
                fn = self._programs[key] = build()
            self.stats["programs"] += 1
        return fn

    def _run_program(self, key, build, *args, **kwargs):
        """Invoke a memoized program; when a profiling capture is open
        (``repro.observe.profile``), note the program + abstract arg
        shapes so the capture can extract its HLO phase map afterwards.
        The None check is the only overhead on the hot path."""
        fn = self._program(key, build)
        cap = _profile.active_capture()
        if cap is not None:
            cap.note_program(fn, args, kwargs)
        return fn(*args, **kwargs)

    def _profiled_run(self, key, build, args, profile_dir: str,
                      entry: str) -> SolveResult:
        """Warm the program, re-run it inside a profiler capture window,
        and attach the analyzed :class:`~repro.observe.profile
        .ProfileReport` as ``self.last_profile`` (also written to
        ``profile_dir/profile.json`` next to the raw timeline)."""
        import os

        fn = self._program(key, build)
        jax.block_until_ready(fn(*args))        # warm: keep compilation
        with _profile.capture(profile_dir) as cap:  # out of the window
            res = fn(*args)
            jax.block_until_ready(res)
            cap.note_program(fn, args)
        iters = int(np.max(np.asarray(res.iterations)))
        rep = cap.analyze(
            iterations=iters or None,
            label=f"{self.method}/{self.sub.name}/{entry}")
        rep.save(os.path.join(profile_dir, "profile.json"))
        cap.save_hlo_map()
        self.last_profile = rep
        return res

    def _mark_trace(self) -> None:
        """Called from inside each program closure: runs once per actual
        jit (re)trace — the amortization metric."""
        self.stats["traces"] += 1
        _metrics.PROGRAM_TRACES.inc()

    def _derive(self, tol, maxiter, trace=None) -> SolverConfig:
        cfg = self.config
        if tol is not None:
            cfg = dataclasses.replace(cfg, tol=float(tol))
        if maxiter is not None:
            cfg = dataclasses.replace(cfg, maxiter=int(maxiter))
        if trace is not None:
            # trace=True -> ring sized to the iteration budget (a full
            # record); an int -> that capacity; False -> force off
            cap = cfg.maxiter if trace is True else int(trace)
            cfg = dataclasses.replace(cfg, trace_cap=cap)
        return cfg

    def _count_solve(self, entry: str) -> None:
        self.stats["solves"] += 1
        _metrics.SOLVES.inc(method=self.method, substrate=self.sub.name,
                            entry=entry)

    @staticmethod
    def _wrap_trace(res: SolveResult) -> SolveResult:
        """ConvergenceTrace at the host boundary (no-op when tracing is
        off — the result is returned as the program produced it)."""
        if res.trace is None:
            return res
        return res._replace(trace=wrap_trace(res.trace))

    def _prep(self, B):
        return B if self._papply is None else self._papply(B)

    def _as_block(self, B) -> jax.Array:
        """Accept an (n, m) block or a sequence of per-column vectors."""
        if isinstance(B, (list, tuple)):
            B = jnp.stack([jnp.asarray(c) for c in B], axis=1)
        else:
            B = jnp.asarray(B)
        if B.ndim != 2:
            raise ValueError(
                f"B must be (n, m) or a sequence of (n,) columns; got "
                f"shape {B.shape}")
        return B

    def _col(self, value, m, default, dtype, *, name="tol"):
        """Materialize a per-column (m,) vector host-side so every solve
        shares one jitted program signature (scalar and None inputs
        broadcast; (m,) vectors pass through; wrong lengths are loud —
        the same :func:`repro.core.types.per_column` contract the
        solvers enforce)."""
        return per_column(default if value is None else value, m, dtype,
                          name=name)

    # -- single-RHS -------------------------------------------------------

    def solve(self, b, x0=None, *, tol=None, maxiter=None,
              r0_star=None, trace=None, profile=None) -> SolveResult:
        """Solve A x = b; the compiled program is cached on the session.

        ``tol``/``maxiter`` override the bound config (each distinct
        override pair compiles its own program — they are static inside
        the solver loop); ``x0``/``r0_star`` as for the free functions.
        ``trace=True`` records the per-iteration convergence trace
        (``SolveResult.trace`` becomes a :class:`repro.observe
        .ConvergenceTrace`); an int keeps only the last that-many
        iterations; the solution is bitwise identical either way (the
        ring buffer is a write-only consumer of values the fused
        reduction already computes — see :mod:`repro.observe`).
        ``profile=dir`` warms the program, re-runs the solve inside a
        :func:`jax.profiler.trace` window, and attaches the analyzed
        per-phase/overlap :class:`~repro.observe.profile.ProfileReport`
        as ``self.last_profile`` (artifacts land under ``dir``).
        """
        if self.blocked:
            raise ValueError(
                "this session wraps a block matvec (blocked=True); "
                "use solve_many / the open-loop handles")
        cfg = self._derive(tol, maxiter, trace)
        key = ("solve", cfg, x0 is None, r0_star is None)

        def build():
            solver = SOLVERS[self.method]

            def solve_program(b, x0, r0s):
                self._mark_trace()
                with internal_use():
                    return solver(self.operator, b, x0, config=cfg,
                                  r0_star=r0s, dot_reduce=self._dot_reduce,
                                  substrate=self.sub, precond=self.precond)
            return jax.jit(solve_program)

        self._count_solve("solve")
        args = (jnp.asarray(b), x0, r0_star)
        if profile is not None:
            return self._wrap_trace(
                self._profiled_run(key, build, args, profile, "solve"))
        return self._wrap_trace(self._run_program(key, build, *args))

    # -- multi-RHS --------------------------------------------------------

    def solve_many(self, B, X0=None, *, tol=None, maxiter=None,
                   r0_star=None, trace=None, profile=None) -> SolveResult:
        """Solve A X = B for all columns at once (ONE (9, m) reduction
        per iteration).

        ``B`` is an (n, m) block or a sequence of per-column (n,)
        vectors.  ``tol``/``maxiter`` may be scalars or per-column (m,)
        vectors; per-column values are runtime arguments, so
        heterogeneous batches share one compiled program.  A scalar
        ``maxiter`` also re-bounds the compiled loop (one program per
        distinct value); per-column ``maxiter`` vectors are capped by
        ``config.maxiter`` — the loop bound — the same way the
        service's resident blocks are.  ``trace`` as in :meth:`solve`;
        the returned :class:`~repro.observe.ConvergenceTrace` is
        batched (``.column(j)`` for per-column views).  ``profile`` as
        in :meth:`solve` (the report's per-iteration numbers use the
        worst column's iteration count).
        """
        self._require_pbicgsafe("solve_many")
        B = self._as_block(B)
        m = B.shape[1]
        if maxiter is not None and np.ndim(maxiter) == 0:
            cfg = self._derive(None, maxiter, trace)
            maxiter = None
        else:
            cfg = self._derive(None, None, trace)
        tol_col = self._col(tol, m, cfg.tol, B.dtype)
        mit_col = self._col(maxiter, m, cfg.maxiter, jnp.int32,
                            name="maxiter")
        key = ("solve_many", cfg, X0 is None, r0_star is None)

        def build():
            def solve_many_program(B, X0, tolv, mitv, r0s):
                self._mark_trace()
                with internal_use():
                    st = init_state(self.block_matvec, self._prep(B), X0,
                                    config=cfg, r0_star=r0s,
                                    dot_reduce=self._dot_reduce,
                                    substrate=self.sub, tol=tolv,
                                    maxiter=mitv)
                    st = step_chunk(self.block_matvec, st, cfg.maxiter,
                                    config=cfg, dot_reduce=self._dot_reduce,
                                    substrate=self.sub)
                return result_from_state(st)
            return jax.jit(solve_many_program)

        self._count_solve("solve_many")
        args = (B, X0, tol_col, mit_col, r0_star)
        if profile is not None:
            return self._wrap_trace(self._profiled_run(
                key, build, args, profile, "solve_many"))
        return self._wrap_trace(self._run_program(key, build, *args))

    # -- open-loop handles (what repro.service drives) --------------------

    def init(self, B, X0=None, *, tol=None, maxiter=None,
             r0_star=None) -> dict:
        """Build the per-column Krylov state pytree for ``A X = B``
        (left-preconditioning of B happens inside the program)."""
        self._require_pbicgsafe("init")
        B = self._as_block(B)
        m = B.shape[1]
        tol_col = self._col(tol, m, self.config.tol, B.dtype)
        mit_col = self._col(maxiter, m, self.config.maxiter,
                            jnp.int32, name="maxiter")
        key = ("init", X0 is None, r0_star is None)

        def build():
            def init_program(B, X0, tolv, mitv, r0s):
                self._mark_trace()
                with internal_use():
                    return init_state(self.block_matvec, self._prep(B), X0,
                                      config=self.config, r0_star=r0s,
                                      dot_reduce=self._dot_reduce,
                                      substrate=self.sub, tol=tolv,
                                      maxiter=mitv)
            return jax.jit(init_program)

        return self._run_program(key, build, B, X0, tol_col, mit_col,
                                 r0_star)

    def _build_step_chunk(self) -> Callable:
        def step_chunk_program(state, k):
            self._mark_trace()
            with internal_use():
                return step_chunk(self.block_matvec, state, k,
                                  config=self.config,
                                  dot_reduce=self._dot_reduce,
                                  substrate=self.sub)
        return jax.jit(step_chunk_program, static_argnames=("k",))

    def compile_step_chunk(self, state: dict, k: int) -> None:
        """Compile the :meth:`step_chunk` program for this state's
        structure without running it (the later call reuses it), so a
        backend's refusal surfaces apart from faults of a run."""
        self._require_pbicgsafe("step_chunk")
        self._program(("step_chunk",), self._build_step_chunk).lower(
            state, k=int(k)).compile()

    def step_chunk(self, state: dict, k: int) -> dict:
        """Advance every live column by up to ``k`` iterations — ONE
        compiled program per k, one (9, m) reduction per iteration."""
        self._require_pbicgsafe("step_chunk")
        return self._run_program(("step_chunk",), self._build_step_chunk,
                                 state, k=int(k))

    def splice(self, state: dict, refill, B_new, *, tol=None,
               maxiter=None, r0_star=None) -> dict:
        """Refill masked columns with fresh (preconditioned-in-program)
        right-hand sides mid-flight; surviving columns are untouched."""
        self._require_pbicgsafe("splice")
        B_new = self._as_block(B_new)
        m = B_new.shape[1]
        tol_col = self._col(tol, m, self.config.tol, B_new.dtype)
        mit_col = self._col(maxiter, m, self.config.maxiter,
                            jnp.int32, name="maxiter")
        key = ("splice", r0_star is None)

        def build():
            def splice_program(state, refill, Bn, tolv, mitv, r0s):
                self._mark_trace()
                with internal_use():
                    return splice_columns(self.block_matvec, state, refill,
                                          self._prep(Bn), r0_star=r0s,
                                          dot_reduce=self._dot_reduce,
                                          substrate=self.sub, tol=tolv,
                                          maxiter=mitv)
            return jax.jit(splice_program)

        return self._run_program(
            key, build, state, jnp.asarray(refill), B_new, tol_col,
            mit_col, r0_star)

    def splice_step(self, state: dict, refill, B_new, tol, maxiter,
                    k: int) -> dict:
        """Fused splice-then-step: admission costs ONE dispatch + one
        host read, same as a chunk without refills (the service engine's
        'one program regardless of request mix' property)."""
        self._require_pbicgsafe("splice_step")
        B_new = self._as_block(B_new)
        m = B_new.shape[1]
        tol_col = self._col(tol, m, self.config.tol, B_new.dtype)
        mit_col = self._col(maxiter, m, self.config.maxiter,
                            jnp.int32, name="maxiter")

        def build():
            def splice_step_program(state, refill, Bn, tolv, mitv, k):
                self._mark_trace()
                with internal_use():
                    st = splice_columns(self.block_matvec, state, refill,
                                        self._prep(Bn),
                                        dot_reduce=self._dot_reduce,
                                        substrate=self.sub, tol=tolv,
                                        maxiter=mitv)
                    return step_chunk(self.block_matvec, st, k,
                                      config=self.config,
                                      dot_reduce=self._dot_reduce,
                                      substrate=self.sub)
            return jax.jit(splice_step_program, static_argnames=("k",))

        return self._run_program(
            ("splice_step",), build, state, jnp.asarray(refill), B_new,
            tol_col, mit_col, k=int(k))

    def verify(self, state: dict, B, mask) -> Tuple[dict, jax.Array]:
        """True-residual check of the masked (converged) columns before
        they are retired: returns ``(state, true_relres)``, with every
        masked column whose true residual misses its ``tol``
        un-converged and residual-replaced (see
        :func:`repro.core.multirhs.verify_columns`; ``B`` is the raw
        right-hand-side block the state was built from)."""
        self._require_pbicgsafe("verify")

        def build():
            def verify_program(state, B, mask):
                self._mark_trace()
                with internal_use():
                    return verify_columns(self.block_matvec, state,
                                          self._prep(B), mask)
            return jax.jit(verify_program)

        return self._run_program(("verify",), build, state,
                                 self._as_block(B), jnp.asarray(mask))

    def result(self, state: dict) -> SolveResult:
        """Package an open-loop state pytree as a :class:`SolveResult`.

        Open-loop tracing is config-driven: bind the session with
        ``SolverConfig(trace_cap=...)`` (or set ``ServiceConfig
        .trace_cap`` on the engine) and every chunk carries the ring
        buffer; this wraps it into a batched
        :class:`~repro.observe.ConvergenceTrace`.
        """
        return self._wrap_trace(result_from_state(state))

    # -- distributed binding ----------------------------------------------

    def on_mesh(self, mesh, *, shard_axes: Optional[Sequence[str]] = None
                ) -> "DistributedSolver":
        """Bind this session to a JAX mesh: returns a
        :class:`DistributedSolver` whose solves shard the grid by rows
        (halo-exchange matvec, ONE psum of the stacked partials per
        reduction phase) with the shard_map program built and cached
        ONCE — the legacy drivers rebuild it per call.

        The binding itself is memoized per (mesh, shard_axes) on the
        session, so calling ``on_mesh`` inside a loop (the literal
        replacement the legacy drivers' deprecation message suggests)
        still reuses the built programs.

        A custom ``dot_reduce`` cannot be honored here — the sharded
        driver's whole point is supplying its own single-psum reduction
        — so binding one is a loud error rather than a silent drop.
        """
        if self._dot_reduce is not identity_reduce:
            raise ValueError(
                "this session binds a custom dot_reduce, which the "
                "distributed driver replaces with its own single psum; "
                "bind the session without dot_reduce= to use .on_mesh")
        key = (mesh, None if shard_axes is None else tuple(shard_axes))
        try:
            hit = self._mesh_bindings.get(key)
        except TypeError:               # unhashable mesh: uncached binding
            return DistributedSolver(self, mesh, shard_axes)
        if hit is None:
            hit = self._mesh_bindings[key] = DistributedSolver(
                self, mesh, shard_axes)
        return hit


class DistributedSolver:
    """A session bound to a mesh: sharded solves from the same front door.

    Wraps :func:`repro.core.distributed.build_stencil_solver` /
    ``build_stencil_solver_batched``; the operator must be a
    :class:`~repro.core.Stencil7Operator` (the row-sharded halo-exchange
    format).  Name-spec preconditioners are rebuilt SHARD-LOCALLY from
    ``precond_spec`` exactly as the legacy drivers do (zero extra
    collectives — the single psum per iteration survives, asserted
    through this binding in tests/test_substrate_parity.py).
    """

    def __init__(self, session: LinearSolver, mesh,
                 shard_axes: Optional[Sequence[str]] = None):
        if not isinstance(session.operator, Stencil7Operator):
            raise TypeError(
                "on_mesh requires a Stencil7Operator-bound session (the "
                f"row-sharded halo format); got "
                f"{type(session.operator).__name__}")
        self.session = session
        self.mesh = mesh
        self.shard_axes = None if shard_axes is None else tuple(shard_axes)
        self._programs: Dict[Any, Callable] = {}

    def _program(self, key, build):
        fn = self._programs.get(key)
        if fn is None:
            fn = self._programs[key] = build()
            self.session.stats["programs"] += 1
        return fn

    def _run_program(self, key, build, *args):
        fn = self._program(key, build)
        cap = _profile.active_capture()
        if cap is not None:
            cap.note_program(fn, args)
        return fn(*args)

    def solve(self, b_grid, *, tol=None, maxiter=None,
              trace=None, profile=None) -> SolveResult:
        """Sharded single-RHS solve of the bound method on the mesh.

        ``trace`` as in :meth:`LinearSolver.solve` — the ring buffer is
        built from psum-replicated scalars, so tracing adds no
        collective (still ONE psum per iteration, contract-verified).
        ``profile=dir`` as in :meth:`LinearSolver.solve`: the captured
        timeline covers every participating device, so here the overlap
        efficiency reads the psum/all-reduce time actually hidden under
        the halo-exchange matvec (report on ``session.last_profile``).
        """
        s = self.session
        cfg = s._derive(tol, maxiter, trace)

        def build():
            from repro.core.distributed import build_stencil_solver
            return build_stencil_solver(
                SOLVERS[s.method], s.operator, self.mesh,
                shard_axes=self.shard_axes, config=cfg, substrate=s.sub,
                precond=s.precond_spec)

        s._count_solve("mesh_solve")
        key = ("dsolve", cfg)
        if profile is not None:
            import os

            fn = self._program(key, build)
            jax.block_until_ready(fn(b_grid))   # warm outside the window
            with _profile.capture(profile) as cap:
                res = fn(b_grid)
                jax.block_until_ready(res)
                cap.note_program(fn, (b_grid,))
            rep = cap.analyze(
                iterations=int(np.max(np.asarray(res.iterations))) or None,
                label=f"{s.method}/{s.sub.name}/mesh_solve")
            rep.save(os.path.join(profile, "profile.json"))
            cap.save_hlo_map()
            s.last_profile = rep
            return s._wrap_trace(res)
        return s._wrap_trace(self._run_program(key, build, b_grid))

    def solve_many(self, B_grid, *, tol=None, maxiter=None,
                   trace=None) -> SolveResult:
        """Sharded batched solve: (nx, ny, nz, m) right-hand sides, ONE
        (9, m) psum per iteration independent of m."""
        s = self.session
        s._require_pbicgsafe("on_mesh(...).solve_many")
        cfg = s._derive(tol, maxiter, trace)

        def build():
            from repro.core.distributed import build_stencil_solver_batched
            return build_stencil_solver_batched(
                s.operator, self.mesh, shard_axes=self.shard_axes,
                config=cfg, substrate=s.sub, precond=s.precond_spec)

        s._count_solve("mesh_solve_many")
        return s._wrap_trace(
            self._run_program(("dsolve_many", cfg), build, B_grid))


# ---------------------------------------------------------------------------
# the session cache (ONE source of truth; service/registry.py consumes it)
# ---------------------------------------------------------------------------

#: LRU-bounded: a long-running process whose operator content evolves
#: (time-stepping coefficients solved one-shot via ``repro.solve``) must
#: not pin every historical operator's arrays + compiled programs until
#: OOM.  Reuse within the bound is the common repeat-traffic case; a
#: live session handed out by make_solver keeps working after eviction —
#: it is simply no longer findable by content.
_SESSION_CACHE_MAX = 64
_SESSIONS: "OrderedDict[Tuple, LinearSolver]" = OrderedDict()


def _substrate_cache_name(sub) -> Optional[str]:
    """Registry substrates are cacheable by name; ad-hoc instances are
    not (their behavior is not content-addressable)."""
    name = getattr(sub, "name", None)
    return name if SUBSTRATES.get(name) is sub else None


def make_solver(method: str = "p-bicgsafe", operator=None, *,
                scenario=None,
                precond: PrecondLike = None,
                substrate: SubstrateLike = "jnp",
                config: SolverConfig = SolverConfig(),
                dot_reduce: Optional[DotReduce] = None,
                blocked: bool = False,
                recovery=None) -> LinearSolver:
    """Bind ``method`` to ``operator`` once; returns a (usually cached)
    :class:`LinearSolver` session.

    Args:
      method: a name from :data:`repro.core.SOLVERS`
        (default ``"p-bicgsafe"``, the paper's method).
      scenario: a registered scenario name or :class:`repro.scenarios
        .Scenario` — the declarative spelling of this whole call: the
        operator is built through its plugin (cached per spec content)
        and method/precond/substrate/config/recovery come from the
        scenario, so every other argument must be left at its default.
        ``make_solver(scenario="poisson-jacobi")`` is
        ``Scenario.bind()`` through the front door, and hits the same
        session cache.
      operator: operator object (Dense/CSR/ELL/Stencil7), dense matrix,
        or bare matvec callable.  Content-addressable operators make the
        session cacheable; callables do not (name-spec preconditioners
        also need an operator object).
      precond: ``None`` | name | :class:`~repro.precond.Preconditioner`.
        Built ONCE here; the distributed binding rebuilds name specs
        shard-locally.
      substrate: ``"jnp"`` | ``"pallas"`` | Substrate instance.
      config: the bound :class:`~repro.core.SolverConfig`
        (``.solve(tol=..., maxiter=...)`` derives overrides per call).
      dot_reduce: custom reduction combiner — sessions with one are
        never cached (callables are not content-addressable).
      blocked: ``operator`` is already an ``(n, m) -> (n, m)`` block
        matvec (advanced; multi-RHS/open-loop entry points only — this
        is the session analogue of ``solve_batched(blocked=True)``).
      recovery: ``None`` | ``True`` | :class:`repro.resilience
        .RecoveryPolicy` — returns a :class:`repro.resilience
        .GuardedSolver` wrapping a guarded session
        (``config.guard=True``; the fused reduction widens to (11, m)
        carrying in-flight health rows) whose chunked driver applies the
        policy's recovery actions — residual replacement, restart,
        method fallback, substrate degradation — at chunk boundaries.
        ``True`` means the default policy.  p-BiCGSafe only (the guard
        rides the batched pipelined iteration).

    Two calls with equal *content* (operator bytes, precond spec,
    substrate name, config, method) return the SAME session — the built
    preconditioner and every compiled program are reused.  This is the
    cache :mod:`repro.service`'s registry consumes.  Guarded wrappers
    are thin, host-side objects built per call; the guarded *session*
    underneath is cached by the same content key.
    """
    if scenario is not None:
        # lazy: repro.scenarios imports this module's public surface
        from repro.scenarios import resolve_scenario
        if operator is not None or method != "p-bicgsafe" \
                or precond is not None or substrate != "jnp" \
                or config != SolverConfig() or dot_reduce is not None \
                or blocked or recovery is not None:
            raise TypeError(
                "make_solver(scenario=...) is exclusive: the scenario "
                "declares the operator, method, precond, substrate, "
                "config and recovery — pass nothing else")
        return resolve_scenario(scenario).bind()
    if operator is None:
        raise TypeError("make_solver requires an operator")
    if recovery is not None and recovery is not False:
        # lazy import: repro.resilience imports repro.api for fallbacks
        from .resilience.guard import GuardedSolver, guarded_config
        from .resilience.policy import RecoveryPolicy
        policy = RecoveryPolicy() if recovery is True else recovery
        if not isinstance(policy, RecoveryPolicy):
            raise TypeError(
                f"recovery must be None, True or a RecoveryPolicy; got "
                f"{type(recovery).__name__}")
        inner = make_solver(method, operator, precond=precond,
                            substrate=substrate,
                            config=guarded_config(config, policy),
                            dot_reduce=dot_reduce, blocked=blocked)
        return GuardedSolver(inner, policy)
    sub = get_substrate(substrate)
    sub_name = _substrate_cache_name(sub)
    try:
        # always computed when the content allows it — consumers (the
        # service registry) key on it even when the SESSION cache below
        # does not apply (custom substrate instance / dot_reduce)
        fingerprint = operator_fingerprint(operator, precond)
    except TypeError:
        fingerprint = None              # bare callables: uncacheable
    key = None
    if dot_reduce is None and sub_name is not None and not blocked \
            and fingerprint is not None \
            and _pytree_is_immutable(operator) \
            and (precond is None or isinstance(precond, str)
                 or _pytree_is_immutable(precond)):
        key = (method, fingerprint, sub_name, config)
        hit = _SESSIONS.get(key)
        if hit is not None:
            _SESSIONS.move_to_end(key)
            _metrics.SESSION_CACHE.inc(outcome="hit")
            return hit
        _metrics.SESSION_CACHE.inc(outcome="miss")
    with _span("api.bind", method=method, substrate=str(sub_name)):
        session = LinearSolver(method, operator, precond=precond,
                               substrate=sub, config=config,
                               dot_reduce=dot_reduce, blocked=blocked,
                               fingerprint=fingerprint)
    if key is not None:
        _SESSIONS[key] = session
        while len(_SESSIONS) > _SESSION_CACHE_MAX:
            _SESSIONS.popitem(last=False)
    return session


def solve(A, b, method: str = "p-bicgsafe", *, x0=None, tol=None,
          maxiter=None, r0_star=None, precond: PrecondLike = None,
          substrate: SubstrateLike = "jnp",
          config: SolverConfig = SolverConfig(),
          dot_reduce: Optional[DotReduce] = None) -> SolveResult:
    """One-shot convenience: ``repro.solve(A, b)``.

    Routes through :func:`make_solver`, so even one-shot callers hit the
    content-keyed session cache — a second ``repro.solve`` against an
    equal-content operator reuses the compiled program and built
    preconditioner instead of retracing.
    """
    session = make_solver(method, A, precond=precond, substrate=substrate,
                          config=config, dot_reduce=dot_reduce)
    return session.solve(b, x0, tol=tol, maxiter=maxiter, r0_star=r0_star)


def clear_session_cache() -> None:
    """Drop every cached session (tests; memory pressure)."""
    _SESSIONS.clear()


def session_cache_info() -> Dict[str, int]:
    return {"sessions": len(_SESSIONS),
            "programs": sum(len(s._programs) for s in _SESSIONS.values())}
