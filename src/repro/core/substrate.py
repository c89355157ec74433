"""Compute substrate: pluggable backend for the solver hot loop.

The per-iteration critical path of every solver in :mod:`repro.core` is
made of three primitive phases:

* ``dots(pairs)``       — stacked local partial inner products (the fused
                          synchronization phase; one ``dot_reduce`` per call),
* ``axpy_phase(...)``   — the blocked vector-update phase,
* ``as_matvec(op)``     — operator -> matvec dispatch (SpMV).

A :class:`Substrate` bundles one implementation of each, so the iteration
bodies are written once against the abstraction and run unchanged on

* ``"jnp"``     — the reference implementation (plain jnp ops; what the
                  solvers inlined historically).  XLA fuses what it can, but
                  the 9-dot phase lowers to 9 separate reductions reading 18
                  operand streams and the Alg. 3.1 update phase to ~10
                  unfused AXPYs.
* ``"pallas"``  — the hand-tiled kernels in :mod:`repro.kernels`: one HBM
                  pass for the 9-dot phase (``fused_dots``), one for the
                  whole vector-update phase (``fused_axpy``), and the banded
                  ELL SpMV (``spmv_ell``).  On TPU these are the compiled
                  Mosaic kernels; elsewhere the same kernel bodies run in
                  interpret mode, so CI exercises them without hardware.

Both substrates keep the solver's communication structure byte-identical:
the fused dot phase still reads only ``{s, y, r, t_prev, rs}`` (no edge to
the in-flight matvec — the paper's overlap property, asserted structurally
in tests/test_substrate_parity.py) and is reduced by the solver's single
``dot_reduce``/``psum``.  Multi-RHS blocks ``(n, m)`` flow through the same
methods and produce ``(k, m)`` partial blocks — still ONE reduction.

Every phase is column-batched on BOTH substrates: ``bicgsafe_dots``
accepts ``(n, m)`` blocks (-> ``(9, m)`` partials), ``axpy_phase`` streams
``(n, m)`` tiles with per-column ``(m,)`` coefficients and an optional
per-column convergence ``mask`` (applied in-kernel on the pallas
substrate), and :meth:`Substrate.as_block_matvec` lifts an operator to
``(n, m) -> (n, m)`` column blocks — for banded ELL operators on the
pallas substrate this is the block-ELL kernel, which reads the matrix
tiles once for all m columns instead of m times.  ``solve_batched`` runs
its entire hot loop through these, so single, batched, distributed, and
batched+distributed solves all execute the same kernel bodies.

Use ``substrate="pallas"`` (or a :class:`Substrate` instance) on any solver
entry point; resolve names with :func:`get_substrate`.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from . import linear_operator
from ._common import local_dots

BICGSAFE_DOT_PAIRS = (
    ("s", "s"), ("y", "y"), ("s", "y"), ("s", "r"), ("y", "r"),
    ("rs", "r"), ("rs", "s"), ("rs", "t"), ("r", "r"))


class Substrate:
    """Strategy object for the solver hot-loop phases.

    Subclasses provide the three primitives; solvers never touch jnp or the
    Pallas kernels directly for these phases.
    """

    name = "abstract"
    #: True when the substrate executes the hand-tiled Pallas kernels —
    #: preconditioners consult this in ``bind`` to pick their kernel path.
    kernel_backed = False

    def dots(self, pairs: Sequence[Tuple[jax.Array, jax.Array]]) -> jax.Array:
        """Stacked local partials <a,b> per pair: (k,) or (k, m) batched."""
        raise NotImplementedError

    def bicgsafe_dots(self, s, y, r, t_prev, rs) -> jax.Array:
        """The 9-dot fused phase of ssBiCGSafe2/p-BiCGSafe.

        Reads ONLY {s, y, r, t_prev, rs} so it carries no dependency edge
        to the iteration's in-flight matvec (the overlap invariant).
        Returns (9,) local partials, or (9, m) for (n, m) multi-RHS blocks.
        """
        raise NotImplementedError

    def bicgsafe_dots_health(self, s, y, r, t_prev, rs, x) -> jax.Array:
        """Guarded fused phase: the 9 dots plus 2 in-reduction health rows.

        Row 9 is ``x.x`` (solution-norm estimate for the drift bound),
        row 10 a NaN/Inf finiteness probe ``sum(s+y+t_prev+rs+x)``.  ``x``
        is the previous iterate (loop-carried), so the phase STILL has no
        dependency edge to the in-flight matvec, and the whole (11,) /
        (11, m) block is reduced by the solver's same single
        ``dot_reduce`` — health monitoring costs zero extra reductions.
        """
        raise NotImplementedError

    def axpy_phase(self, vecs: dict, scalars, mask=None) -> dict:
        """p-BiCGSafe's blocked vector-update phase (Alg. 3.1 lines 23-32).

        vecs: dict with r,p,u,t,y,z,s,l,g,w,x,As; scalars: (alpha, beta,
        zeta, eta).  Returns dict with the primed p,o,u,q,w,t,z,y,x,r.

        Multi-RHS: ``(n, m)`` blocks with ``(m,)`` per-column scalars, and
        an optional ``(m,)`` bool ``mask`` — frozen (mask=False) columns
        keep their input values for every output with same-named state.
        """
        raise NotImplementedError

    def check_dtype(self, dtype) -> None:
        """Raise ``ValueError`` at bind time when this substrate cannot
        compile the hot loop for operands of ``dtype`` on this backend."""

    def as_matvec(self, op):
        """Operator / matrix / callable -> matvec callable."""
        return linear_operator.as_matvec(op)

    def as_block_matvec(self, op):
        """Operator -> column-blocked matvec ``(n, m) -> (n, m)``.

        Default: vmap the single-vector matvec over columns
        (:func:`repro.core.multirhs.batched_matvec` — the canonical
        lift).  Substrates with a dedicated block SpMV kernel override
        this so the matrix is streamed once for all m right-hand sides.
        """
        from .multirhs import batched_matvec   # lazy: multirhs imports us
        return batched_matvec(self.as_matvec(op))

    def as_precond_apply(self, pc):
        """Preconditioner -> substrate-routed M^{-1}-apply callable.

        Delegates to ``pc.bind(self)`` so kernel dispatch lives with each
        preconditioner class (:mod:`repro.precond`): block-Jacobi binds
        the Pallas batched block-apply kernel on kernel-backed substrates,
        Neumann builds its series on this substrate's (block) matvec, and
        elementwise/shift applies stay jnp (XLA fuses them).  The bound
        apply is shape-polymorphic over ``(n,)`` / ``(n, m)`` operands
        and contains NO inner products — preconditioning never changes
        the solver's ``dot_reduce`` count.
        """
        return pc.bind(self)

    def __repr__(self):
        return f"<{type(self).__name__} {self.name!r}>"


class JnpSubstrate(Substrate):
    """Reference substrate: the historical inline-jnp hot loop."""

    name = "jnp"

    def dots(self, pairs):
        return local_dots(pairs)

    def bicgsafe_dots(self, s, y, r, t_prev, rs):
        v = dict(s=s, y=y, r=r, t=t_prev, rs=rs)
        return local_dots([(v[a], v[b]) for a, b in BICGSAFE_DOT_PAIRS])

    def bicgsafe_dots_health(self, s, y, r, t_prev, rs, x):
        v = dict(s=s, y=y, r=r, t=t_prev, rs=rs)
        base = local_dots(
            [(v[a], v[b]) for a, b in BICGSAFE_DOT_PAIRS] + [(x, x)])
        comb = s + y + t_prev + rs + x
        probe = jnp.sum(comb, axis=0) if comb.ndim == 2 else jnp.sum(comb)
        return jnp.concatenate([base, probe[None]])

    def axpy_phase(self, vecs, scalars, mask=None):
        from repro.kernels import ref
        return ref.fused_axpy(vecs, scalars, mask=mask)


class PallasSubstrate(Substrate):
    """Pallas-kernel substrate (compiled on TPU, interpret mode elsewhere).

    The 9-dot phase and the vector-update phase each become one fused
    kernel pass; ELL operators with a banded structure dispatch to the
    Pallas SpMV.  Phases with no dedicated kernel (the 1-5 dot phases of
    the BiCGStab/GPBi-CG family) fall back to the jnp reference — they are
    not the paper's hot path.
    """

    name = "pallas"
    kernel_backed = True

    def check_dtype(self, dtype):
        from repro.kernels import ops
        if dtype is not None and jnp.dtype(dtype) == jnp.float64 \
                and not ops._interpret():
            raise ValueError(
                "substrate='pallas' cannot compile float64 kernels on TPU "
                "(Mosaic is 32-bit); bind a float32 operator or "
                "substrate='jnp'")

    def dots(self, pairs):
        return local_dots(pairs)

    def bicgsafe_dots(self, s, y, r, t_prev, rs):
        from repro.kernels import ops
        return ops.fused_dots(s, y, r, t_prev, rs)

    def bicgsafe_dots_health(self, s, y, r, t_prev, rs, x):
        from repro.kernels import ops
        return ops.fused_dots_health(s, y, r, t_prev, rs, x)

    def axpy_phase(self, vecs, scalars, mask=None):
        from repro.kernels import ops
        return ops.fused_axpy(vecs, scalars, mask=mask)

    def as_matvec(self, op):
        from repro.kernels import ops
        if isinstance(op, linear_operator.ELLOperator) \
                and ops.ell_is_banded(op):
            return functools.partial(ops.spmv_ell, op)
        return linear_operator.as_matvec(op)

    def as_block_matvec(self, op):
        from repro.kernels import ops
        if isinstance(op, linear_operator.ELLOperator) \
                and ops.ell_is_banded(op):
            # ops.spmv_ell handles (n, m) via the block kernel directly —
            # NOT a vmap of the 1-D kernel, which would re-read values/cols
            # once per column
            return functools.partial(ops.spmv_ell, op)
        return super().as_block_matvec(op)


SUBSTRATES = {
    "jnp": JnpSubstrate(),
    "pallas": PallasSubstrate(),
}

SubstrateLike = Union[str, Substrate, None]


def get_substrate(spec: SubstrateLike) -> Substrate:
    """Resolve a substrate name / instance / None (-> ``"jnp"``)."""
    if spec is None:
        return SUBSTRATES["jnp"]
    if isinstance(spec, Substrate):
        return spec
    try:
        return SUBSTRATES[spec]
    except KeyError:
        raise ValueError(
            f"unknown substrate {spec!r}; expected one of "
            f"{sorted(SUBSTRATES)} or a Substrate instance") from None
