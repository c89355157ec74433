"""Shared machinery for the Krylov solvers.

The central abstraction is the *fused dot phase*: every solver computes all
inner products of one synchronization phase as a single stacked vector of
local partial sums and calls ``dot_reduce`` exactly once on it.  Standalone,
``dot_reduce`` is the identity (the dots are already global); inside the
``shard_map``-distributed driver it is a single ``lax.psum`` — one global
reduction per phase, which is the paper's communication model.  The number
of ``dot_reduce`` calls per iteration therefore *is* the solver's
synchronization count (1 for ssBiCGSafe2/p-BiCGSafe, 2 for BiCGStab and
p-BiCGStab, 3 for GPBi-CG), and tests assert it.

*Who computes the partials* is pluggable: the solvers obtain their local
partial sums (and the Alg. 3.1 vector-update phase) from a compute
substrate (:mod:`repro.core.substrate`) — ``"jnp"`` produces them with the
plain jnp ops below, ``"pallas"`` with the fused one-HBM-pass kernels in
:mod:`repro.kernels`.  Either way the stacked-partials contract is
identical, so ``dot_reduce`` semantics and the synchronization counts are
substrate-independent.

Multi-RHS: every helper here is column-batched.  ``local_dots`` accepts
``(n, m)`` operand blocks and yields ``(k, m)`` stacked partials (one
column of dots per right-hand side, still one reduction), and
``bicgsafe_coefficients`` broadcasts elementwise over trailing RHS axes —
this is what :func:`repro.core.multirhs.solve_batched` runs on.

Supported path matrix (every cell runs the SAME iteration body; the
substrate picks who computes the vector phases, the driver picks where):

====================  =======================  ==========================
scenario              ``substrate="jnp"``      ``substrate="pallas"``
====================  =======================  ==========================
single RHS            inline jnp ops           fused_dots / fused_axpy /
                                               banded spmv_ell kernels
batched (n, m)        jnp broadcasting         (n, m) block kernels:
                                               fused_dots_batched,
                                               fused_axpy_batched (with
                                               the per-column convergence
                                               mask in-kernel), block-ELL
                                               spmv_ell_batched
distributed           per-shard jnp + 1 psum   per-shard kernels + 1 psum
batched+distributed   row-sharded (n, m),      row-sharded block kernels,
                      1 psum of (9, m)/iter    1 psum of (9, m)/iter
====================  =======================  ==========================

(``distributed_stencil_solve`` / ``distributed_stencil_solve_batched`` in
:mod:`repro.core.distributed`; the single psum per iteration and its
independence from the in-flight matvec hold in every cell — asserted in
tests/test_substrate_parity.py, tests/_distributed_check.py and
benchmarks/bench_overlap.py.)

The batched row is also exposed open-loop — ``multirhs.init_state`` /
``step_chunk`` / ``splice_columns`` — which is what the
continuous-batching solve service (:mod:`repro.service`) drives: one
resident (n, max_batch) block per operator, heterogeneous requests
multiplexed onto its columns, same single (9, m) reduction and overlap
structure per iteration (asserted on the engine's step program in
tests/test_service.py).

Preconditioning (the ``precond=`` column of every cell above; see
:mod:`repro.precond`) — how each M^{-1}-apply executes per substrate,
and its distributed locality:

==============  ==========================  =======================  ============
preconditioner  ``substrate="jnp"``         ``substrate="pallas"``   distributed
==============  ==========================  =======================  ============
jacobi          elementwise jnp (fused      same (no kernel needed)  exact,
                by XLA)                                              shard-local
block_jacobi    batched jnp einsum          Pallas batched           exact,
                                            block-apply kernel       shard-local
                                            (shared-block case:
                                            one MXU matmul)
neumann         jnp matvec series           series on the Pallas     shard-local
                                            SpMV / block-ELL         (additive-
                                            kernels (banded ELL)     Schwarz)
ssor            stencil shifts (jnp,        same jnp body (no        shard-local
                XLA-fused)                  dedicated kernel)        (additive-
                                                                     Schwarz)
==============  ==========================  =======================  ============

Every apply is shape-polymorphic over ``(n,)`` / ``(n, m)`` operands,
contains no inner products (the dot_reduce/psum counts above are
precond-independent), and — composed as ``M^{-1} ∘ A`` — sits inside the
pipelined solvers' overlap window, so the single reduction keeps no
dependency edge to the in-flight precond+matvec (asserted in
tests/test_substrate_parity.py and benchmarks/_overlap_child.py).
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .types import SolverConfig


def local_dots(pairs: Sequence[Tuple[jax.Array, jax.Array]],
               dtype=None) -> jax.Array:
    """Stack partial inner products <a,b> for each pair into one vector.

    On a sharded vector this yields the *local* partial sums; a single
    reduction of the stacked vector produces every global inner product of
    the phase at once (8 scalars -> one 8-word message, as in the paper).

    ``(n, m)`` multi-RHS operands produce a ``(len(pairs), m)`` block of
    per-column dots — the same single reduction then serves all m systems.
    """
    outs = []
    for a, b in pairs:
        if a.ndim == 2:
            acc = jnp.sum(a * b, axis=0, dtype=dtype)
        elif dtype is not None:
            acc = jnp.sum(a * b, dtype=dtype)
        else:
            # HIGHEST: a TPU's default f32 dot rounds operands to bf16
            acc = jnp.vdot(a, b, precision=lax.Precision.HIGHEST)
        outs.append(acc)
    return jnp.stack(outs)


def safe_div(num: jax.Array, den: jax.Array, eps: float):
    """num/den with breakdown detection: returns (value, is_breakdown)."""
    bad = jnp.abs(den) <= eps
    val = num / jnp.where(bad, jnp.ones_like(den), den)
    return jnp.where(bad, jnp.zeros_like(val), val), bad


def init_guess(b: jax.Array, x0: Optional[jax.Array]) -> jax.Array:
    return jnp.zeros_like(b) if x0 is None else x0.astype(b.dtype)


def tree_select(pred, on_true, on_false):
    """Elementwise select over matching pytrees (pred is a scalar bool)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), on_true, on_false)


def bicgsafe_coefficients(dots: jax.Array, i: jax.Array,
                          alpha_prev, zeta_prev, f_prev, eps: float):
    """Coefficients shared by ssBiCGSafe2 (Alg 2.3) and p-BiCGSafe (Alg 3.1).

    ``dots = [a, b, c, d, e, f, g, h, rr]`` with
      a=(s,s) b=(y,y) c=(s,y) d=(s,r) e=(y,r)
      f=(r0*,r) g=(r0*,s) h=(r0*,t_{i-1}) rr=(r,r).

    i = 0:  beta=0, alpha=f/g, zeta=d/a, eta=0          (paper lines 10-14)
    i > 0:  beta=(alpha_{i-1} f)/(zeta_{i-1} f_{i-1}),
            alpha=f/(g + beta h),
            zeta=(b d - c e)/(a b - c^2),
            eta =(a e - c d)/(a b - c^2)                (paper lines 16-20)

    Returns (beta, alpha, zeta, eta, f, rr, breakdown).
    """
    a, b, c, d, e, f, g, h, rr = (dots[k] for k in range(9))
    first = i == 0

    beta_g, bad_beta = safe_div(alpha_prev * f, zeta_prev * f_prev, eps)
    beta = jnp.where(first, jnp.zeros_like(f), beta_g)

    alpha, bad_alpha = safe_div(f, g + beta * h, eps)

    zeta0, bad_z0 = safe_div(d, a, eps)
    denom = a * b - c * c
    zeta_g, bad_zg = safe_div(b * d - c * e, denom, eps)
    eta_g, _ = safe_div(a * e - c * d, denom, eps)
    zeta = jnp.where(first, zeta0, zeta_g)
    eta = jnp.where(first, jnp.zeros_like(f), eta_g)

    breakdown = jnp.where(
        first, bad_z0 | bad_alpha,
        bad_beta | bad_alpha | bad_zg)
    return beta, alpha, zeta, eta, f, rr, breakdown


def bicgsafe_breakdown_code(dots: jax.Array, i: jax.Array,
                            alpha_prev, zeta_prev, f_prev,
                            eps: float) -> jax.Array:
    """Typed cause of a BiCGSafe coefficient breakdown, as an int32
    :class:`repro.core.types.SolveStatus` code (0 == no breakdown).

    Recomputes the same three denominators :func:`bicgsafe_coefficients`
    guards with ``safe_div`` (XLA CSEs the shared subexpressions, so this
    adds a handful of scalar compares, no vector work) and names the
    first offender in precedence order rho -> alpha -> omega, matching
    the ``breakdown`` flag's ``first``/``i>0`` gating exactly:

    * BREAKDOWN_RHO:   beta denominator ``zeta_{i-1} * f_{i-1}`` (i > 0)
    * BREAKDOWN_ALPHA: alpha denominator ``g + beta * h`` (incl. the
      i == 0 pivot ``(s,s)`` of ``zeta_0 = d/a``)
    * BREAKDOWN_OMEGA: zeta/eta denominator ``a*b - c^2`` (i > 0)
    """
    from .types import SolveStatus
    a, b, c, d, e, f, g, h, rr = (dots[k] for k in range(9))
    del d, e, rr
    first = i == 0

    bad_rho = (~first) & (jnp.abs(zeta_prev * f_prev) <= eps)
    beta_g, _ = safe_div(alpha_prev * f, zeta_prev * f_prev, eps)
    beta = jnp.where(first, jnp.zeros_like(f), beta_g)
    bad_alpha = jnp.abs(g + beta * h) <= eps
    bad_pivot = jnp.where(first, jnp.abs(a) <= eps,
                          jnp.abs(a * b - c * c) <= eps)

    code = jnp.where(bad_pivot, SolveStatus.BREAKDOWN_OMEGA.value, 0)
    code = jnp.where(first & bad_pivot, SolveStatus.BREAKDOWN_ALPHA.value,
                     code)
    code = jnp.where(bad_alpha, SolveStatus.BREAKDOWN_ALPHA.value, code)
    code = jnp.where(bad_rho, SolveStatus.BREAKDOWN_RHO.value, code)
    return code.astype(jnp.int32)


def pipelined_recurrence_tail(q, s, As, g, Aw, alpha, zeta, eta):
    """p-BiCGSafe's recurred A-images after MV #2 (Aw = A w_i).

    Returns (l, g_next, s_next) per Eqns. 3.7 / 3.10 / 3.2:
    l_i == A t_i, g_{i+1} == A y_{i+1}, s_{i+1} == A r_{i+1}.
    Shared by the single-RHS solver and the batched multi-RHS solver
    (scalars may be () or (m,); (m,) broadcasts over (n, m) blocks).
    """
    l = q - Aw
    g_next = zeta * As + eta * g - alpha * Aw
    s_next = s - alpha * q - g_next
    return l, g_next, s_next


class SyncCounter:
    """Trace-time counter of dot_reduce invocations (sync phases/iter)."""

    def __init__(self, reduce_fn):
        self._fn = reduce_fn
        self.calls = 0

    def __call__(self, partials):
        self.calls += 1
        return self._fn(partials)
