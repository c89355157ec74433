"""Distributed solver runtime: shard_map + halo exchange + single psum.

Block domain decomposition of the 3-D grid over an arbitrary JAX mesh.
Each grid axis is split over the mesh axes its *layout* names
(:func:`grid_layout`): a right-hand side laid out by a ``NamedSharding``
on the mesh keeps its blocks (``P("x", "y", "z")`` on a 2x2x1 mesh gives
each device a 2x2 block of whole z-columns); one without takes x-slabs,
the grid's x-axis split over *all* mesh axes flattened row-major.

One halo exchange serves every stencil operator (:func:`halo_pad`): it
brings one plane across each split grid axis in turn -- x, then y on the
x-extended block, then z -- so edges and corners arrive without diagonal
messages, and the operator applies its stencil to the padded block
(``apply_padded``).  A plane travels by :func:`ring_shift`, a
nearest-neighbour ``ppermute`` on the flattened ring of the axis's mesh
axes, implemented recursively so it works on 1-, 2- ((data, model)) and
3-axis ((pod, data, model)) production meshes — the wrap slab cascades
to the next outer axis exactly like a carry.

The inner-product phases of the solvers call ``dot_reduce`` once per phase;
here that is **one ``lax.psum`` of the stacked partials over the split
mesh axes** — the paper's single global reduction.  Because p-BiCGSafe's
dots do not consume the in-flight matvec, the lowered HLO contains no
dependency path from that all-reduce to the halo ppermutes / stencil
compute, which is what lets the XLA latency-hiding scheduler overlap them
(verified structurally in benchmarks/bench_overlap.py).

:func:`distributed_stencil_solve_batched` extends the same decomposition
to multi-RHS blocks: the (n, m) block is sharded in x-slabs, the halo
exchange carries all m columns in one ppermute cascade, and the single
psum now reduces the (9, m) partial block — communication per iteration
is independent of m, and the overlap property survives (same structural
proof, batched entry).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..precond.base import PrecondLike, resolve_precond
from . import _deprecation
from .linear_operator import Stencil7Operator, Stencil27Operator
from .types import SolveResult, SolverConfig

StencilOperator = Union[Stencil7Operator, Stencil27Operator]
#: the mesh axes each grid axis (x, y, z) is split over
Layout = Tuple[Tuple[str, ...], Tuple[str, ...], Tuple[str, ...]]
#: per grid axis, the (mesh axes, their sizes) of its split, both empty
#: where the axis is whole on every device
Split = Tuple[Tuple[Tuple[str, ...], Tuple[int, ...]], ...]
GRID_AXES = ("x", "y", "z")


def _shard_local_precond(precond: PrecondLike, local_op: StencilOperator):
    """Resolve ``precond`` against the LOCAL block operator.

    Name specs build from the shard's own block operator (``local_op``,
    the global one's coefficients on the block's shape), so every
    preconditioner is communication-free by construction: its arrays
    describe one block and its apply touches no mesh axis (the
    per-iteration psum count is therefore unchanged — asserted in
    tests/_distributed_check.py).  For ``"jacobi"`` this is *exact* (the
    diagonal is constant), as is ``"block_jacobi"`` where z is not split
    (z-line blocks never straddle a block boundary); for ``"neumann"``
    and ``"ssor"`` it is the shard-local (zero-Dirichlet at block
    boundaries) additive-Schwarz flavor of the global preconditioner —
    still a fixed linear M^{-1}, just a slightly weaker one.  A
    :class:`Stencil27Operator` takes ``"jacobi"`` only.

    A :class:`~repro.precond.Preconditioner` instance is passed through
    untouched; its arrays must already be local-block sized (or
    shard-shape-free, like a shared (1, bs, bs) block).
    """
    if not isinstance(precond, str):
        return precond
    if isinstance(local_op, Stencil27Operator) and precond != "jacobi":
        raise ValueError(
            f"precond={precond!r} has no shard-local form for a "
            "Stencil27Operator on a mesh; use None or 'jacobi'")
    return resolve_precond(precond, local_op)


# ---------------------------------------------------------------------------
# decomposition
# ---------------------------------------------------------------------------

def _axis_sizes(mesh: Mesh, axes: Sequence[str]) -> Tuple[int, ...]:
    return tuple(mesh.shape[a] for a in axes)


def grid_layout(mesh: Mesh, b=None,
                shard_axes: Optional[Sequence[str]] = None) -> Layout:
    """The mesh axes each grid axis is split over.

    ``shard_axes`` given: x-slabs over those axes.  Otherwise ``b``'s own
    blocks, where it carries a ``NamedSharding`` on ``mesh`` that splits
    a grid axis; otherwise x-slabs over every mesh axis.
    """
    if shard_axes is None:
        sharding = getattr(b, "sharding", None)
        if isinstance(sharding, NamedSharding) and sharding.mesh == mesh:
            spec = (tuple(sharding.spec) + (None,) * 3)[:3]
            layout = tuple(() if s is None else (s,) if isinstance(s, str)
                           else tuple(s) for s in spec)
            if any(layout):
                return layout
        shard_axes = mesh.axis_names
    return (tuple(shard_axes), (), ())


def decompose(mesh: Mesh, layout: Layout, grid: Sequence[int]
              ) -> Tuple[Split, Tuple[int, int, int]]:
    """``(split, local_shape)`` of ``grid`` laid out by ``layout``.

    Raises ``ValueError`` where a grid axis does not divide by the
    number of its shards.
    """
    split, local = [], []
    for name, axes, n in zip(GRID_AXES, layout, grid):
        sizes = _axis_sizes(mesh, axes)
        shards = math.prod(sizes)
        if n % shards:
            raise ValueError(
                f"grid axis {name} has {n} points, which do not split over "
                f"its {shards} shards (mesh axes {axes})")
        split.append((tuple(axes), sizes) if shards > 1 else ((), ()))
        local.append(n // shards)
    return tuple(split), tuple(local)


def _spec(layout: Layout) -> P:
    return P(*[(axes[0] if len(axes) == 1 else axes) if axes else None
               for axes in layout])


def _note_halo_bytes(split: Split, local_shape: Sequence[int],
                     itemsize: int, columns: int = 1) -> None:
    """Set ``repro_mesh_halo_bytes{axis}``: the bytes one device sends
    per matvec along each grid axis — a plane of the block (extended
    along the axes exchanged before it) to each neighbour it has there,
    one where the axis has two shards, none where it is whole."""
    from repro.observe.metrics import MESH_HALO_BYTES
    ext = list(local_shape)
    for d, (axes, sizes) in enumerate(split):
        planes = min(2, math.prod(sizes) - 1) if axes else 0
        MESH_HALO_BYTES.set(planes * math.prod(ext) // ext[d] * columns
                            * itemsize, axis=GRID_AXES[d])
        ext[d] += 2


# ---------------------------------------------------------------------------
# halo exchange
# ---------------------------------------------------------------------------

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


def halo_pad(u: jax.Array, split: Split) -> jax.Array:
    """The local block ``u`` (grid axes first, then any column axes) with
    one plane more on each side of each grid axis: the neighbouring
    block's plane across a split axis, zeros at the global boundary and
    along whole axes.  Axes are exchanged in turn, each on the block
    already extended along the ones before it, so edges and corners
    arrive without diagonal messages."""
    for d, (axes, sizes) in enumerate(split):
        n = u.shape[d]
        first = lax.slice_in_dim(u, 0, 1, axis=d)
        last = lax.slice_in_dim(u, n - 1, n, axis=d)
        if axes:
            with jax.named_scope("repro.halo"):
                lo = ring_shift(last, axes, sizes, forward=True)
                hi = ring_shift(first, axes, sizes, forward=False)
        else:
            lo = hi = jnp.zeros_like(first)
        u = jnp.concatenate([lo, u, hi], axis=d)
    return u


def halo_matvec(local_op: StencilOperator, split: Split,
                u_flat: jax.Array) -> jax.Array:
    """The stencil matvec of one device's block: halo exchange, then the
    operator's stencil on the padded block.

    Communication: one plane to each neighbour along each split axis —
    the O(surface) cost that the paper's SpMV hides the O(1) reduction
    message behind.  ``u_flat`` may be a multi-RHS block
    ``(n_local, m)``: the halo ppermutes carry the trailing column axis
    along, so one exchange serves all m right-hand sides.
    """
    shape = (local_op.nx, local_op.ny, local_op.nz)
    u = u_flat.reshape(*shape, *u_flat.shape[1:])
    return local_op.apply_padded(halo_pad(u, split)).reshape(u_flat.shape)


# ---------------------------------------------------------------------------
# distributed solve driver
# ---------------------------------------------------------------------------

def build_stencil_solver(solver: Callable,
                         op: StencilOperator,
                         mesh: Mesh,
                         *,
                         shard_axes: Optional[Sequence[str]] = None,
                         layout: Optional[Layout] = None,
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

    ``layout`` (:func:`grid_layout`) names the mesh axes each grid axis
    is split over; b is taken, and x returned, in those blocks.  Default:
    x-slabs over ``shard_axes`` (every mesh axis when None).
    """
    if layout is None:
        layout = grid_layout(mesh, shard_axes=shard_axes)
    split, local_shape = decompose(mesh, layout, (op.nx, op.ny, op.nz))
    local_op = type(op)(op.c, *local_shape)
    pc = _shard_local_precond(precond, local_op)
    reduce_axes = tuple(a for axes in layout for a in axes)

    def dot_reduce(partials):
        return lax.psum(partials, reduce_axes)   # ONE reduction for all dots

    def shard_fn(b_local):
        mv = functools.partial(halo_matvec, local_op, split)
        with _deprecation.internal_use():
            res = solver(mv, b_local.reshape(-1), config=config,
                         dot_reduce=dot_reduce, substrate=substrate,
                         precond=pc)
        return res._replace(x=res.x.reshape(local_shape))

    _note_halo_bytes(split, local_shape, jnp.dtype(op.dtype).itemsize)
    spec = _spec(layout)
    # the trace ring buffer is built from psum-replicated dot-derived
    # scalars, so every shard holds the same buffer: replicated specs
    out_specs = SolveResult(
        x=spec, iterations=P(), relres=P(), converged=P(),
        breakdown=P(), residual_history=P(), status=P(),
        trace={"buffer": P(), "steps": P()} if config.trace_cap else None)

    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(spec,),
                       out_specs=out_specs, check_vma=False)
    if jit:
        fn = jax.jit(fn)
    return fn


def distributed_stencil_solve(solver: Callable,
                              op: StencilOperator,
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

    ``precond`` is resolved against the LOCAL block operator
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


def build_stencil_solver_batched(op: StencilOperator,
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
    B is taken in x-slabs over ``shard_axes`` (every mesh axis when None).
    """
    from .multirhs import solve_batched

    layout = grid_layout(mesh, shard_axes=shard_axes)
    split, local_shape = decompose(mesh, layout, (op.nx, op.ny, op.nz))
    n_local = math.prod(local_shape)
    local_op = type(op)(op.c, *local_shape)
    # shard-local preconditioner (shape-polymorphic apply: the same bound
    # M^{-1} serves the (n_local, m) block — one build for all m columns)
    pc = _shard_local_precond(precond, local_op)
    reduce_axes = layout[0]

    def dot_reduce(partials):
        return lax.psum(partials, reduce_axes)   # ONE reduction: (9, m)

    def shard_fn(b_local):
        m = b_local.shape[-1]
        _note_halo_bytes(split, local_shape, b_local.dtype.itemsize, m)
        mv = functools.partial(halo_matvec, local_op, split)
        # NOTE: no r0_star passthrough — a global shadow vector would have
        # to be row-sharded alongside B for the per-shard partial dots to
        # be correct; the default (RS = R0, already local) is what the
        # single-RHS driver uses too.
        with _deprecation.internal_use():
            res = solve_batched(mv, b_local.reshape(n_local, m),
                                config=config, dot_reduce=dot_reduce,
                                substrate=substrate, blocked=True, precond=pc)
        return res._replace(x=res.x.reshape(*local_shape, m))

    in_specs = _spec(layout)
    # the trace ring buffer is built from psum-replicated dot-derived
    # scalars, so every shard holds the same buffer: replicated specs
    out_specs = SolveResult(
        x=in_specs, iterations=P(), relres=P(), converged=P(),
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


def distributed_stencil_solve_batched(op: StencilOperator,
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
