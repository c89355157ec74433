"""Distributed solver runtime: shard_map + halo exchange + single psum.

Row-block domain decomposition over an arbitrary JAX mesh.  The grid's
x-dimension is sharded over *all* mesh axes (flattened, row-major); halo
exchange is a nearest-neighbour ``ppermute`` on the flattened logical ring,
implemented recursively so it works on 1-, 2- ((data, model)) and
3-axis ((pod, data, model)) production meshes — the wrap slab cascades to
the next outer axis exactly like a carry.

The inner-product phases of the solvers call ``dot_reduce`` once per phase;
here that is **one ``lax.psum`` of the stacked partials over the whole
mesh** — the paper's single global reduction.  Because p-BiCGSafe's dots do
not consume the in-flight matvec, the lowered HLO contains no dependency
path from that all-reduce to the halo ppermutes / stencil compute, which is
what lets the XLA latency-hiding scheduler overlap them (verified
structurally in benchmarks/bench_overlap.py).

:func:`distributed_stencil_solve_batched` extends the same decomposition
to multi-RHS blocks: the (n, m) block is row-sharded, the halo exchange
carries all m columns in one ppermute cascade, and the single psum now
reduces the (9, m) partial block — communication per iteration is
independent of m, and the overlap property survives (same structural
proof, batched entry).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..precond.base import PrecondLike, resolve_precond
from . import _deprecation
from .linear_operator import Stencil7Operator
from .types import SolveResult, SolverConfig


def _shard_local_precond(precond: PrecondLike, c: jax.Array,
                         local_shape: Tuple[int, int, int]):
    """Resolve ``precond`` against the LOCAL slab operator.

    Name specs build from the shard's own ``(nxl, ny, nz)`` stencil
    operator, so every preconditioner is communication-free by
    construction: its arrays describe one slab and its apply touches no
    mesh axis (the per-iteration psum count is therefore unchanged —
    asserted in tests/_distributed_check.py).  For ``"jacobi"`` and
    ``"block_jacobi"`` this is *exact* (the diagonal is constant and
    z-line blocks never straddle x-slab boundaries); for ``"neumann"``
    and ``"ssor"`` it is the shard-local (zero-Dirichlet at slab
    boundaries) additive-Schwarz flavor of the global preconditioner —
    still a fixed linear M^{-1}, just a slightly weaker one.

    A :class:`~repro.precond.Preconditioner` instance is passed through
    untouched; its arrays must already be local-slab sized (or
    shard-shape-free, like a shared (1, bs, bs) block).
    """
    if not isinstance(precond, str):
        return precond
    local_op = Stencil7Operator(c, *local_shape)
    return resolve_precond(precond, local_op)


# ---------------------------------------------------------------------------
# flattened-ring halo exchange
# ---------------------------------------------------------------------------

def _axis_sizes(mesh: Mesh, axes: Sequence[str]) -> Tuple[int, ...]:
    return tuple(mesh.shape[a] for a in axes)


def ring_shift(x: jax.Array, axes: Sequence[str], sizes: Sequence[int],
               forward: bool) -> jax.Array:
    """Shift ``x`` by one position along the flattened (row-major) mesh ring.

    ``forward`` sends to linear index +1 (receiver gets its left/lower
    neighbour's slab); missing senders at the global boundary yield zeros
    (Dirichlet).  Recursion: a within-axis shift on the innermost axis plus
    a wrap term that moves the last-position slab to position 0 and then
    ring-shifts it along the remaining outer axes.
    """
    axis, size = axes[-1], sizes[-1]
    if forward:
        within_perm = [(i, i + 1) for i in range(size - 1)]
        wrap_perm = [(size - 1, 0)]
    else:
        within_perm = [(i + 1, i) for i in range(size - 1)]
        wrap_perm = [(0, size - 1)]

    within = lax.ppermute(x, axis, within_perm) if within_perm else \
        jnp.zeros_like(x)
    if len(axes) == 1:
        return within
    wrap = lax.ppermute(x, axis, wrap_perm)
    wrap = ring_shift(wrap, axes[:-1], sizes[:-1], forward)
    return within + wrap


def halo_stencil_matvec(c: jax.Array, u_flat: jax.Array,
                        local_shape: Tuple[int, int, int],
                        axes: Sequence[str], sizes: Sequence[int]) -> jax.Array:
    """7-point stencil matvec on the local x-slab with ring halo exchange.

    Communication: two 1-slab ppermute cascades (up & down neighbours) of
    ny*nz elements each — the O(surface) cost that the paper's SpMV hides
    the O(1) reduction message behind.

    ``u_flat`` may be a multi-RHS block ``(nxl*ny*nz, m)``: the stencil and
    the halo ppermutes carry the trailing column axis along, so one halo
    cascade serves all m right-hand sides (the per-column communication
    cost is amortized m-fold, mirroring the batched reduction).
    """
    nxl, ny, nz = local_shape
    u = u_flat.reshape(nxl, ny, nz, *u_flat.shape[1:])

    # x-direction halos from the flattened ring
    top = u[-1:]      # sent forward: becomes receiver's u[x-1] slab
    bot = u[:1]       # sent backward: becomes receiver's u[x+1] slab
    halo_lo = ring_shift(top, axes, sizes, forward=True)    # u[i-1] at i=0
    halo_hi = ring_shift(bot, axes, sizes, forward=False)   # u[i+1] at i=nxl-1

    um = jnp.concatenate([halo_lo, u[:-1]], axis=0)
    up = jnp.concatenate([u[1:], halo_hi], axis=0)
    zy = jnp.zeros_like(u[:, :1])
    vm = jnp.concatenate([zy, u[:, :-1]], axis=1)
    vp = jnp.concatenate([u[:, 1:], zy], axis=1)
    zz = jnp.zeros_like(u[:, :, :1])
    wm = jnp.concatenate([zz, u[:, :, :-1]], axis=2)
    wp = jnp.concatenate([u[:, :, 1:], zz], axis=2)

    out = (c[0] * u + c[1] * um + c[2] * up + c[3] * vm + c[4] * vp
           + c[5] * wm + c[6] * wp)
    return out.reshape(u_flat.shape)


# ---------------------------------------------------------------------------
# distributed solve driver
# ---------------------------------------------------------------------------

def build_stencil_solver(solver: Callable,
                         op: Stencil7Operator,
                         mesh: Mesh,
                         *,
                         shard_axes: Optional[Sequence[str]] = None,
                         config: SolverConfig = SolverConfig(),
                         substrate: str = "jnp",
                         precond: PrecondLike = None,
                         jit: bool = True) -> Callable:
    """Build the sharded solve program ``fn(b_grid) -> SolveResult``.

    This is the reusable half of :func:`distributed_stencil_solve`: the
    shard-local preconditioner is resolved and the shard_map program is
    constructed ONCE; the returned (jitted) callable is what a bound
    session (:meth:`repro.api.LinearSolver.on_mesh`) caches so repeat
    sharded solves stop paying per-call retracing.
    """
    axes = tuple(shard_axes if shard_axes is not None else mesh.axis_names)
    sizes = _axis_sizes(mesh, axes)
    n_shards = int(np.prod(sizes))
    nx, ny, nz = op.nx, op.ny, op.nz
    if nx % n_shards:
        raise ValueError(f"nx={nx} not divisible by {n_shards} shards")
    local_shape = (nx // n_shards, ny, nz)
    c = op.c
    pc = _shard_local_precond(precond, c, local_shape)

    def dot_reduce(partials):
        return lax.psum(partials, axes)   # ONE reduction for all dots

    def shard_fn(b_local):
        mv = functools.partial(halo_stencil_matvec, c,
                               local_shape=local_shape, axes=axes, sizes=sizes)
        with _deprecation.internal_use():
            res = solver(mv, b_local.reshape(-1), config=config,
                         dot_reduce=dot_reduce, substrate=substrate,
                         precond=pc)
        return res._replace(x=res.x.reshape(local_shape))

    in_specs = P(axes)
    # the trace ring buffer is built from psum-replicated dot-derived
    # scalars, so every shard holds the same buffer: replicated specs
    out_specs = SolveResult(
        x=P(axes), iterations=P(), relres=P(), converged=P(),
        breakdown=P(), residual_history=P(), status=P(),
        trace={"buffer": P(), "steps": P()} if config.trace_cap else None)

    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(in_specs,),
                       out_specs=out_specs, check_vma=False)
    if jit:
        fn = jax.jit(fn)
    return fn


def distributed_stencil_solve(solver: Callable,
                              op: Stencil7Operator,
                              b_grid: jax.Array,
                              mesh: Mesh,
                              *,
                              shard_axes: Optional[Sequence[str]] = None,
                              config: SolverConfig = SolverConfig(),
                              substrate: str = "jnp",
                              precond: PrecondLike = None,
                              jit: bool = True):
    """Solve the stencil system on ``mesh`` with any solver from repro.core.

    ``b_grid`` has shape (nx, ny, nz); its x-dimension is sharded over
    ``shard_axes`` (default: every mesh axis, row-major).  Returns a
    :class:`SolveResult` whose ``x`` is the sharded solution grid.

    ``substrate`` selects the per-shard compute substrate
    (:mod:`repro.core.substrate`): the fused dot partials and vector
    updates inside each shard come from that substrate, while the global
    reduction stays this driver's single ``psum`` either way.

    ``precond`` is resolved against the LOCAL slab operator
    (:func:`_shard_local_precond`), so every preconditioner apply is
    shard-local — zero extra communication and an unchanged single psum
    per reduction phase.

    Deprecated as a direct entry point: this shim rebuilds (and
    retraces) the shard_map program on every call.  A mesh-bound session
    — ``repro.make_solver(method, op).on_mesh(mesh)`` — builds it once
    and reuses the compiled program.
    """
    _deprecation.warn_legacy(
        "distributed_stencil_solve",
        "repro.make_solver(method, op).on_mesh(mesh)")
    return build_stencil_solver(
        solver, op, mesh, shard_axes=shard_axes, config=config,
        substrate=substrate, precond=precond, jit=jit)(b_grid)


def build_stencil_solver_batched(op: Stencil7Operator,
                                 mesh: Mesh,
                                 *,
                                 shard_axes: Optional[Sequence[str]] = None,
                                 config: SolverConfig = SolverConfig(),
                                 substrate: str = "jnp",
                                 precond: PrecondLike = None,
                                 jit: bool = True) -> Callable:
    """Build the sharded batched solve program ``fn(B_grid) -> SolveResult``.

    The reusable half of :func:`distributed_stencil_solve_batched` (see
    :func:`build_stencil_solver`); the returned callable accepts any
    column count m — ``jax.jit`` keys the compiled program by shape.
    """
    from .multirhs import solve_batched

    axes = tuple(shard_axes if shard_axes is not None else mesh.axis_names)
    sizes = _axis_sizes(mesh, axes)
    n_shards = int(np.prod(sizes))
    nx, ny, nz = op.nx, op.ny, op.nz
    if nx % n_shards:
        raise ValueError(f"nx={nx} not divisible by {n_shards} shards")
    local_shape = (nx // n_shards, ny, nz)
    n_local = local_shape[0] * ny * nz
    c = op.c
    # shard-local preconditioner (shape-polymorphic apply: the same bound
    # M^{-1} serves the (n_local, m) block — one build for all m columns)
    pc = _shard_local_precond(precond, c, local_shape)

    def dot_reduce(partials):
        return lax.psum(partials, axes)   # ONE reduction: the (9, m) block

    def shard_fn(b_local):
        m = b_local.shape[-1]
        mv = functools.partial(halo_stencil_matvec, c,
                               local_shape=local_shape, axes=axes, sizes=sizes)
        # NOTE: no r0_star passthrough — a global shadow vector would have
        # to be row-sharded alongside B for the per-shard partial dots to
        # be correct; the default (RS = R0, already local) is what the
        # single-RHS driver uses too.
        with _deprecation.internal_use():
            res = solve_batched(mv, b_local.reshape(n_local, m),
                                config=config, dot_reduce=dot_reduce,
                                substrate=substrate, blocked=True, precond=pc)
        return res._replace(x=res.x.reshape(*local_shape, m))

    in_specs = P(axes)
    # the trace ring buffer is built from psum-replicated dot-derived
    # scalars, so every shard holds the same buffer: replicated specs
    out_specs = SolveResult(
        x=P(axes), iterations=P(), relres=P(), converged=P(),
        breakdown=P(), residual_history=P(), status=P(),
        trace={"buffer": P(), "steps": P()} if config.trace_cap else None)

    sharded = jax.shard_map(shard_fn, mesh=mesh, in_specs=(in_specs,),
                            out_specs=out_specs, check_vma=False)

    def fn(B_grid):
        if B_grid.ndim != 4:
            raise ValueError(
                f"B_grid must be (nx, ny, nz, m); got {B_grid.shape}")
        return sharded(B_grid)

    if jit:
        fn = jax.jit(fn)
    return fn


def distributed_stencil_solve_batched(op: Stencil7Operator,
                                      B_grid: jax.Array,
                                      mesh: Mesh,
                                      *,
                                      shard_axes: Optional[Sequence[str]] = None,
                                      config: SolverConfig = SolverConfig(),
                                      substrate: str = "jnp",
                                      precond: PrecondLike = None,
                                      jit: bool = True):
    """Batched multi-RHS stencil solve sharded over ``mesh``.

    ``B_grid`` has shape (nx, ny, nz, m): the x-dimension is sharded over
    ``shard_axes`` (default: every mesh axis, row-major) exactly as in
    :func:`distributed_stencil_solve`, and the m right-hand-side columns
    stay local to every shard — the sharded state block is the
    (n_local, m) tile the batched kernels stream.

    Communication per iteration is identical to the single-RHS distributed
    solve: one halo ppermute cascade per block matvec (carrying all m
    columns at once) and ONE ``psum`` — now of the ``(9, m)`` partial
    block, so the per-iteration synchronization cost is amortized over all
    m systems while the no-dependency-edge overlap with the in-flight
    block matvec is preserved (asserted structurally in
    tests/test_substrate_parity.py and benchmarks/bench_overlap.py).

    Returns a :class:`SolveResult` whose ``x`` is the sharded
    (nx, ny, nz, m) solution grid; per-column ``iterations``/``relres``/
    ``converged``/``breakdown`` are replicated.

    Deprecated as a direct entry point (rebuilds the shard_map program
    per call): use ``repro.make_solver("p-bicgsafe", op).on_mesh(mesh)
    .solve_many(B_grid)``, which caches the built program.
    """
    _deprecation.warn_legacy(
        "distributed_stencil_solve_batched",
        'repro.make_solver("p-bicgsafe", op).on_mesh(mesh).solve_many(B)')
    if B_grid.ndim != 4:
        raise ValueError(f"B_grid must be (nx, ny, nz, m); got {B_grid.shape}")
    return build_stencil_solver_batched(
        op, mesh, shard_axes=shard_axes, config=config, substrate=substrate,
        precond=precond, jit=jit)(B_grid)


def replicated_dot_reduce(axes):
    """dot_reduce for custom shard_map code: one psum over ``axes``."""
    return lambda partials: lax.psum(partials, axes)
