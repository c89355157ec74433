"""Linear operators for the Krylov solvers.

All operators expose ``matvec`` (jit/vmap-safe pure function of a vector),
``shape`` and ``dtype``.  Sparse formats:

* :class:`CSROperator` — classic compressed sparse row; gather + segment
  sum.  Reference format (CPU-friendly; what PETSc used in the paper).
* :class:`ELLOperator` — ELLPACK: fixed ``k`` nonzeros per row stored as two
  dense ``(n, k)`` arrays.  Dense regular layout → maps directly onto TPU
  VMEM tiles; this is the format the Pallas SpMV kernel consumes.
* :class:`Stencil7Operator` — matrix-free 7-point (3-D) finite-difference
  operator with optional convection (non-symmetric) terms; the structured
  analogue of the paper's fluid-dynamics matrices, and the operator used by
  the distributed halo-exchange path.

Design note: operators are pytrees (registered dataclasses) so they can be
closed over or passed as arguments to jitted solvers and sharded with
shard_map.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .types import MatVec


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DenseOperator:
    """Dense matrix operator (small systems / tests)."""

    a: jax.Array

    @property
    def shape(self):
        return self.a.shape

    @property
    def dtype(self):
        return self.a.dtype

    # HIGHEST: a TPU's default f32 dot rounds operands to bf16
    def matvec(self, x: jax.Array) -> jax.Array:
        return jnp.matmul(self.a, x, precision=lax.Precision.HIGHEST)

    def rmatvec(self, x: jax.Array) -> jax.Array:
        return jnp.matmul(self.a.T, x, precision=lax.Precision.HIGHEST)

    def diagonal(self) -> jax.Array:
        return jnp.diagonal(self.a)

    def tree_flatten(self):
        return (self.a,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CSROperator:
    """CSR sparse operator.

    ``data``/``indices`` are nnz-length; ``row_ids`` is the expanded row
    index per nonzero (precomputed from indptr so matvec is a pure gather +
    segment_sum with static shapes — no dynamic loops).
    """

    data: jax.Array      # (nnz,)
    indices: jax.Array   # (nnz,) int32 column ids
    row_ids: jax.Array   # (nnz,) int32 row ids
    n: int               # static number of rows/cols

    @property
    def shape(self):
        return (self.n, self.n)

    @property
    def dtype(self):
        return self.data.dtype

    def matvec(self, x: jax.Array) -> jax.Array:
        prods = self.data * x[self.indices]
        return jax.ops.segment_sum(prods, self.row_ids, num_segments=self.n)

    def rmatvec(self, x: jax.Array) -> jax.Array:
        prods = self.data * x[self.row_ids]
        return jax.ops.segment_sum(prods, self.indices, num_segments=self.n)

    def diagonal(self) -> jax.Array:
        on_diag = jnp.where(self.indices == self.row_ids, self.data, 0.0)
        return jax.ops.segment_sum(on_diag, self.row_ids, num_segments=self.n)

    @staticmethod
    def from_scipy(m) -> "CSROperator":
        m = m.tocsr()
        n = m.shape[0]
        indptr = np.asarray(m.indptr)
        row_ids = np.repeat(np.arange(n, dtype=np.int32),
                            np.diff(indptr).astype(np.int32))
        return CSROperator(jnp.asarray(m.data), jnp.asarray(m.indices, jnp.int32),
                           jnp.asarray(row_ids), n)

    def tree_flatten(self):
        return (self.data, self.indices, self.row_ids), self.n

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n=aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ELLOperator:
    """ELLPACK operator: fixed k nonzeros/row, padded with zeros.

    TPU-friendly: ``values``/``cols`` are dense (n, k) arrays so the SpMV is
    a gather + row reduction over a regular layout (Pallas kernel target).
    Padding entries have ``cols == pad_col`` (their value is 0 so any column
    works; we use 0).
    """

    values: jax.Array  # (n, k)
    cols: jax.Array    # (n, k) int32
    n: int

    @property
    def shape(self):
        return (self.n, self.n)

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def k(self) -> int:
        return self.values.shape[1]

    def matvec(self, x: jax.Array) -> jax.Array:
        return jnp.sum(self.values * x[self.cols], axis=1)

    def diagonal(self) -> jax.Array:
        row = jnp.arange(self.n)[:, None]
        return jnp.sum(jnp.where(self.cols == row, self.values, 0.0), axis=1)

    @staticmethod
    def from_csr(op: CSROperator, k: Optional[int] = None) -> "ELLOperator":
        """Convert (host-side) a CSR operator to padded ELL."""
        data = np.asarray(op.data)
        indices = np.asarray(op.indices)
        row_ids = np.asarray(op.row_ids)
        n = op.n
        counts = np.bincount(row_ids, minlength=n)
        kk = int(counts.max()) if k is None else k
        values = np.zeros((n, kk), dtype=data.dtype)
        cols = np.zeros((n, kk), dtype=np.int32)
        # position of each nnz within its row
        pos = np.arange(len(data)) - np.concatenate(
            ([0], np.cumsum(counts)[:-1]))[row_ids]
        values[row_ids, pos] = data
        cols[row_ids, pos] = indices
        return ELLOperator(jnp.asarray(values), jnp.asarray(cols), n)

    def tree_flatten(self):
        return (self.values, self.cols), self.n

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n=aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Stencil7Operator:
    """Matrix-free 7-point stencil on an (nx, ny, nz) grid.

    A = -div(grad u) * diag_scale + convection  (Dirichlet boundaries).

    ``c`` holds the 7 coefficients (center, ±x, ±y, ±z); allowing
    asymmetric off-diagonal pairs gives a non-symmetric matrix
    (convection–diffusion), the paper's dominant matrix kind.

    Vectors are flattened (nx*ny*nz,); matvec reshapes internally.  This
    operator is also the one the distributed driver shards by x-slabs with
    ppermute halo exchange.
    """

    c: jax.Array  # (7,) [center, xm, xp, ym, yp, zm, zp]
    nx: int
    ny: int
    nz: int

    @property
    def n(self):
        return self.nx * self.ny * self.nz

    @property
    def shape(self):
        return (self.n, self.n)

    @property
    def dtype(self):
        return self.c.dtype

    def matvec(self, x: jax.Array) -> jax.Array:
        u = x.reshape(self.nx, self.ny, self.nz)
        c = self.c
        out = c[0] * u
        # zero-Dirichlet shifts (no wraparound): pad+slice
        zx = jnp.zeros_like(u[:1])
        um = jnp.concatenate([zx, u[:-1]], axis=0)   # u[i-1]
        up = jnp.concatenate([u[1:], zx], axis=0)    # u[i+1]
        zy = jnp.zeros_like(u[:, :1])
        vm = jnp.concatenate([zy, u[:, :-1]], axis=1)
        vp = jnp.concatenate([u[:, 1:], zy], axis=1)
        zz = jnp.zeros_like(u[:, :, :1])
        wm = jnp.concatenate([zz, u[:, :, :-1]], axis=2)
        wp = jnp.concatenate([u[:, :, 1:], zz], axis=2)
        out = out + c[1] * um + c[2] * up + c[3] * vm + c[4] * vp \
            + c[5] * wm + c[6] * wp
        return out.reshape(-1)

    def diagonal(self) -> jax.Array:
        return jnp.full((self.n,), self.c[0], dtype=self.dtype)

    def tree_flatten(self):
        return (self.c,), (self.nx, self.ny, self.nz)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def as_matvec(op) -> MatVec:
    """Accept an operator object, a dense matrix, or a callable."""
    if callable(op) and not hasattr(op, "matvec"):
        return op
    if hasattr(op, "matvec"):
        return op.matvec
    a = jnp.asarray(op)
    return lambda x: jnp.matmul(a, x, precision=lax.Precision.HIGHEST)


# -- deprecation re-exports ---------------------------------------------------
# The preconditioning machinery moved to the repro.precond subsystem
# (PR 3): JacobiPreconditioner gained a dtype-preserving zero-diagonal
# guard + (n, m) multi-RHS applies there, and preconditioned_matvec is
# superseded by precond= on a bound session (repro.make_solver), which
# keeps operator dispatch to the Pallas kernels and routes the
# M^{-1}-apply through the compute substrate.  PEP 562 module
# __getattr__ keeps the historical import path working but announces the
# move with one DeprecationWarning per process instead of aliasing
# silently (identity is preserved: the returned objects ARE the
# repro.precond ones).

def __getattr__(name: str):
    from ._deprecation import warn_legacy
    if name == "preconditioned_matvec":
        warn_legacy("repro.core.linear_operator.preconditioned_matvec",
                    'precond= on repro.make_solver(...) '
                    "(or repro.precond.preconditioned_matvec)")
        from repro.precond.base import preconditioned_matvec
        return preconditioned_matvec
    if name == "JacobiPreconditioner":
        warn_legacy("repro.core.linear_operator.JacobiPreconditioner",
                    "repro.precond.JacobiPreconditioner")
        from repro.precond.jacobi import JacobiPreconditioner
        return JacobiPreconditioner
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
