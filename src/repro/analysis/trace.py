"""Trace session bindings into analyzable jaxprs — zero solver executions.

The contract passes are *static*: they consume the jaxpr of a solver
program, never its outputs.  :func:`trace_binding` builds that jaxpr for
any cell of the scenario matrix (method x substrate x binding kind x
guard x precond x mesh) with two instrumentation tags, both implemented
with ``lax.optimization_barrier`` (semantically the identity, so the
traced program IS the production program's dataflow):

* every ``dot_reduce`` call is tagged together with a ``(13,)`` marker
  constant — a shape no solver's partial block can collide with (the
  widest fused phase is the guarded ``(11, m)``) — so reduction phases
  are identifiable in the while body regardless of the method's partial
  shapes;
* the operator's matvec output is tagged bare, so the overlap pass can
  ask whether a reduction transitively consumes the in-flight matvec.

Mesh bindings need no tags: there the reduction IS the ``psum``
primitive and the halo exchange IS ``ppermute``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import SOLVERS, SolverConfig
from repro.core._deprecation import internal_use
from repro.core.linear_operator import Stencil7Operator, Stencil27Operator
from repro.core.multirhs import init_state, solve_batched, step_chunk
from repro.core.substrate import get_substrate

from .jaxpr_tools import find_while_body
from .report import BindingSpec

__all__ = ["TracedBinding", "trace_binding", "trace_fn",
           "REDUCE_MARK_DIM", "tag_reduce", "tag_matvec"]

#: marker length for reduction tags; no solver partial block has a
#: leading dim of 13 (max is the guarded 11), so the marker output
#: uniquely identifies reduce-tag equations in the while body.
REDUCE_MARK_DIM = 13


def tag_reduce(partials):
    """A ``dot_reduce`` that tags the fused partial block in the jaxpr."""
    mark = jnp.zeros((REDUCE_MARK_DIM,), partials.dtype)
    out, _ = lax.optimization_barrier((partials, mark))
    return out


def tag_matvec(mv: Callable) -> Callable:
    """Wrap a matvec so its output is barrier-tagged in the jaxpr."""
    return lambda x: lax.optimization_barrier(mv(x))


@dataclasses.dataclass
class TracedBinding:
    """One traced session binding: the analyzer's input unit."""

    spec: BindingSpec
    jaxpr: Any                       # the ClosedJaxpr of the whole program
    body: Any                        # the while-loop body jaxpr (or None)

    # -- tag accessors (local bindings) -----------------------------------

    def _barrier_eqns(self) -> List:
        if self.body is None:
            return []
        return [e for e in self.body.eqns
                if e.primitive.name == "optimization_barrier"]

    def reduce_eqns(self) -> List:
        """Reduction-phase equations in the while body: the marker-tagged
        barriers (local bindings) or the ``psum`` equations (mesh)."""
        if self.spec.binding == "mesh":
            from .jaxpr_tools import find_prim_eqns
            return [] if self.body is None \
                else find_prim_eqns(self.body, "psum")
        return [e for e in self._barrier_eqns()
                if len(e.outvars) >= 2
                and tuple(e.outvars[-1].aval.shape) == (REDUCE_MARK_DIM,)]

    def matvec_tag_eqns(self) -> List:
        """Matvec-output tags in the while body (local bindings only)."""
        return [e for e in self._barrier_eqns()
                if not (len(e.outvars) >= 2 and
                        tuple(e.outvars[-1].aval.shape)
                        == (REDUCE_MARK_DIM,))]


def _operator_matvec(operator) -> Callable:
    if hasattr(operator, "matvec"):
        return operator.matvec
    if callable(operator):
        return operator
    raise TypeError(
        f"cannot trace operator of type {type(operator).__name__}: "
        "need .matvec or a callable")


def _operator_dim(operator, n: Optional[int]) -> int:
    if n is not None:
        return int(n)
    if hasattr(operator, "shape"):
        return int(operator.shape[0])
    for attr in ("n",):
        if hasattr(operator, attr):
            return int(getattr(operator, attr))
    raise ValueError(
        "cannot infer the operator dimension for tracing; pass n= "
        "(bare-callable operators carry no shape)")


def _float_dtype():
    import numpy as np
    return jax.dtypes.canonicalize_dtype(np.float64)   # f64 under x64


def _precond_kernel_count(pc, sub) -> int:
    """Pallas kernels the bound preconditioner is expected to add to the
    iteration body.  Only block-Jacobi has a dedicated apply kernel, and
    only when its blocks actually vary (nb > 1): the shared-block case is
    one dense matmul the kernel layer deliberately routes to the
    reference path (XLA maps it onto the MXU already) — policy, not a
    silent fallback."""
    if pc is None or not getattr(sub, "kernel_backed", False):
        return 0
    from repro.precond.block_jacobi import BlockJacobiPreconditioner
    if isinstance(pc, BlockJacobiPreconditioner) \
            and pc.inv_blocks.shape[0] > 1:
        return 1
    return 0


def _resolve_precond_instance(precond, operator):
    """Build a name-spec preconditioner against the REAL operator (the
    probe hands the solver a tagged matvec closure, which a name spec
    could not build from); instances pass through."""
    if precond is None or not isinstance(precond, str):
        return precond
    from repro.precond.base import resolve_precond
    return resolve_precond(precond, operator)


def trace_fn(fn: Callable, *args, spec: BindingSpec) -> TracedBinding:
    """Trace an arbitrary probe function into a :class:`TracedBinding`.

    The low-level entry the pass-level unit tests use to hand-build
    violating programs; :func:`trace_binding` routes everything through
    it too.
    """
    with internal_use():
        closed = jax.make_jaxpr(fn)(*args)
    return TracedBinding(spec=spec, jaxpr=closed,
                         body=find_while_body(closed.jaxpr))


def trace_binding(method: str,
                  operator,
                  *,
                  binding: str = "single",
                  substrate: str = "jnp",
                  precond=None,
                  guard: bool = False,
                  m: int = 3,
                  n: Optional[int] = None,
                  config: Optional[SolverConfig] = None,
                  mesh=None,
                  blocked: bool = False) -> TracedBinding:
    """Trace one scenario-matrix cell.  Tracing only — no solve runs.

    Args:
      method: a name from :data:`repro.core.SOLVERS`.
      operator: operator object (preferred; preconditioner name specs
        and mesh bindings need one) or a bare matvec callable (with
        ``n=``).
      binding: ``"single"`` | ``"batched"`` | ``"open_loop"`` (the
        service-chunk program) | ``"mesh"`` (the sharded batched driver;
        requires a stencil operator and ``mesh=``).
      guard: trace with ``SolverConfig.guard`` — the (11, m) fused
        phase on the bindings that support it (recorded as
        ``spec.guard_effective``).
      precond: ``None`` | name | Preconditioner instance.
      m: column count for batched/open-loop/mesh bindings.
      blocked: ``operator`` is already an (n, m) -> (n, m) block matvec.
    """
    if method not in SOLVERS:
        raise ValueError(f"unknown method {method!r}")
    if binding not in ("single", "batched", "open_loop", "mesh"):
        raise ValueError(f"unknown binding kind {binding!r}")
    sub = get_substrate(substrate)
    cfg = config if config is not None else SolverConfig(maxiter=8)
    if guard != cfg.guard:
        cfg = dataclasses.replace(cfg, guard=guard)
    precond_name = precond if isinstance(precond, str) else (
        getattr(precond, "name", None) if precond is not None else None)
    guard_effective = bool(guard) and binding in ("batched", "open_loop",
                                                  "mesh")
    dtype = _float_dtype()

    if binding == "mesh":
        if mesh is None:
            raise ValueError("binding='mesh' requires mesh=")
        if not isinstance(operator, (Stencil7Operator, Stencil27Operator)):
            raise TypeError("binding='mesh' requires a Stencil7Operator or "
                            "a Stencil27Operator")
        from repro.core.distributed import (build_stencil_solver,
                                            build_stencil_solver_batched)
        spec = BindingSpec(method=method, substrate=sub.name, binding="mesh",
                           guard=guard, precond=precond_name, m=m,
                           mesh_shape=tuple(mesh.devices.shape),
                           guard_effective=guard_effective)
        op = operator
        if method == "p-bicgsafe":
            B_grid = jnp.ones((op.nx, op.ny, op.nz, m), dtype)
            with internal_use():
                fn = build_stencil_solver_batched(
                    op, mesh, config=cfg, substrate=sub.name,
                    precond=precond, jit=False)
            return trace_fn(fn, B_grid, spec=spec)
        b_grid = jnp.ones((op.nx, op.ny, op.nz), dtype)
        with internal_use():
            fn = build_stencil_solver(SOLVERS[method], op, mesh, config=cfg,
                                      substrate=sub.name, precond=precond,
                                      jit=False)
        return trace_fn(fn, b_grid, spec=dataclasses.replace(spec, m=1))

    pc = _resolve_precond_instance(precond, operator)
    dim = _operator_dim(operator, n)
    precond_kernels = _precond_kernel_count(pc, sub)

    if binding == "single":
        if blocked:
            raise ValueError("binding='single' cannot trace a block matvec")
        mv = tag_matvec(_operator_matvec(operator))
        b = jnp.ones((dim,), dtype)
        spec = BindingSpec(method=method, substrate=sub.name,
                           binding="single", guard=guard,
                           precond=precond_name, m=1,
                           guard_effective=False,
                           precond_kernels=precond_kernels)

        def run(bb):
            return SOLVERS[method](mv, bb, config=cfg,
                                   dot_reduce=tag_reduce, substrate=sub,
                                   precond=pc)
        return trace_fn(run, b, spec=spec)

    # batched / open_loop: the p-BiCGSafe block iteration only
    if method != "p-bicgsafe":
        raise ValueError(
            f"binding={binding!r} runs the batched p-BiCGSafe iteration "
            f"only (got method={method!r})")
    if blocked:
        bmv = tag_matvec(operator)
    else:
        bmv = tag_matvec(sub.as_block_matvec(operator))
    B = jnp.ones((dim, m), dtype)
    spec = BindingSpec(method=method, substrate=sub.name, binding=binding,
                       guard=guard, precond=precond_name, m=m,
                       guard_effective=guard_effective,
                       precond_kernels=precond_kernels)

    if binding == "batched":
        def run(BB):
            return solve_batched(bmv, BB, config=cfg, dot_reduce=tag_reduce,
                                 substrate=sub, blocked=True, precond=pc)
        return trace_fn(run, B, spec=spec)

    # open_loop: the service-chunk program — init fused into the chunk so
    # tracing never executes a matvec eagerly
    papply = None if pc is None else sub.as_precond_apply(pc)

    def run(BB):
        BB = BB if papply is None else papply(BB)
        st = init_state(bmv, BB, config=cfg, dot_reduce=tag_reduce,
                        substrate=sub)
        return step_chunk(bmv, st, cfg.maxiter, config=cfg,
                          dot_reduce=tag_reduce, substrate=sub)
    return trace_fn(run, B, spec=spec)
