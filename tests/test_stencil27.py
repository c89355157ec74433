"""Stencil27Operator: HPCG's 27-point operator against a matrix
assembled entry by entry, its two arithmetic forms, its one-device
solve, and (in a subprocess with four host devices,
tests/_stencil27_mesh_check.py) its sharded solve on 2x2x1, 4x1x1 and
1x2x2 process grids."""
import itertools
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Var

import repro
from repro import SolverConfig, Stencil27Operator
from repro.observe.metrics import STENCIL_FORM

HERE = os.path.dirname(__file__)
SRC = os.path.abspath(os.path.join(HERE, os.pardir, "src"))


def assembled(c, nx, ny, nz):
    """The dense matrix, row by row over the 27 offsets: a row keeps
    only the neighbours inside the grid (HPCG's GenerateProblem_ref)."""
    n = nx * ny * nz
    a = np.zeros((n, n))
    idx = np.arange(n).reshape(nx, ny, nz)
    for i, j, k in itertools.product(range(nx), range(ny), range(nz)):
        for dx, dy, dz in itertools.product((-1, 0, 1), repeat=3):
            ii, jj, kk = i + dx, j + dy, k + dz
            if 0 <= ii < nx and 0 <= jj < ny and 0 <= kk < nz:
                a[idx[i, j, k], idx[ii, jj, kk]] = c[dx + 1, dy + 1, dz + 1]
    return a


def random_op(nx, ny, nz, seed=3):
    c = np.random.default_rng(seed).uniform(-1.0, 1.0, (3, 3, 3))
    return Stencil27Operator(jnp.asarray(c, jnp.float32), nx, ny, nz)


@pytest.mark.parametrize("make", [Stencil27Operator.hpcg, random_op],
                         ids=["hpcg", "random"])
def test_matvec_is_the_assembled_matrix(make):
    op = make(7, 6, 5)
    a = assembled(np.asarray(op.c, np.float64), 7, 6, 5)
    x = np.random.default_rng(0).standard_normal(op.n).astype(np.float32)
    got = np.asarray(op.matvec(jnp.asarray(x)))
    np.testing.assert_allclose(got, a @ x, rtol=1e-5, atol=1e-5)
    assert op.shape == a.shape and op.dtype == jnp.float32


def equal_op(nx, ny, nz):
    """Centre 3.0, every neighbour -0.1: a second equal-off-centre set."""
    c = np.full((3, 3, 3), -0.1)
    c[1, 1, 1] = 3.0
    return Stencil27Operator(jnp.asarray(c, jnp.float32), nx, ny, nz)


def padded_block(op, columns, seed=6):
    """A padded block whose halo planes are non-zero, as a neighbour's
    are on a mesh."""
    shape = (op.nx + 2, op.ny + 2, op.nz + 2) + columns
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def traced_apply(op, p):
    """``apply_padded`` with the operator passed into ``jax.jit`` as an
    argument: ``c`` is a tracer, so the general form."""
    return jax.jit(lambda o, v: o.apply_padded(v))(op, p)


def form_counts():
    return {f: STENCIL_FORM.value(operator="stencil27", form=f)
            for f in ("box", "general")}


@pytest.mark.parametrize("columns", [(), (3,)], ids=["vector", "columns3"])
@pytest.mark.parametrize("make", [Stencil27Operator.hpcg, equal_op],
                         ids=["hpcg", "equal"])
def test_box_form_is_the_general_form(make, columns):
    """Equal off-centre coefficients take the box form, which gives the
    27-term sum to float32 rounding, trailing columns included."""
    op = make(9, 8, 7)
    p = padded_block(op, columns)
    assert op.form() == "box"
    box = np.asarray(op.apply_padded(p))
    general = np.asarray(traced_apply(op, p))
    assert box.shape == general.shape == (9, 8, 7) + columns
    np.testing.assert_allclose(box, general, rtol=1e-5,
                               atol=1e-5 * np.abs(general).max())


def p_slices(closed):
    """The ``slice`` equations whose operand is the padded block or was
    computed from it (the last input), not from the coefficients."""
    jaxpr = closed.jaxpr
    derived, n = {jaxpr.invars[-1]}, 0
    for eqn in jaxpr.eqns:
        if any(v in derived for v in eqn.invars if isinstance(v, Var)):
            derived.update(eqn.outvars)
            n += eqn.primitive.name == "slice"
    return n


@pytest.mark.parametrize("case,form", [
    ("hpcg", "box"), ("equal", "box"), ("random", "general"),
    ("traced", "general")])
def test_apply_takes_the_form_its_coefficients_allow(case, form):
    """One traced apply counts once in ``repro_stencil_form_total``, in
    the form its coefficients allow: the box form slices the padded
    block at most 10 times (3 a sum, 1 for u), the general form 27."""
    make = {"hpcg": Stencil27Operator.hpcg, "equal": equal_op,
            "random": random_op, "traced": Stencil27Operator.hpcg}[case]
    op = make(9, 8, 7)
    p = padded_block(op, ())
    before = form_counts()
    if case == "traced":
        closed = jax.make_jaxpr(lambda o, v: o.apply_padded(v))(op, p)
    else:
        assert op.form() == form
        closed = jax.make_jaxpr(op.apply_padded)(p)
    after = form_counts()
    assert {f: after[f] - before[f] for f in after} == {
        f: float(f == form) for f in after}
    n = p_slices(closed)
    assert n <= 10 if form == "box" else n == 27, (case, n)


def test_diagonal():
    for op in (Stencil27Operator.hpcg(7, 6, 5), random_op(7, 6, 5)):
        a = assembled(np.asarray(op.c, np.float64), 7, 6, 5)
        np.testing.assert_allclose(np.asarray(op.diagonal()), np.diag(a),
                                   rtol=1e-6)


def test_hpcg_row_sums():
    """0 in the interior, 26 - 7 = 19 at a corner (7 neighbours)."""
    op = Stencil27Operator.hpcg(7, 6, 5)
    sums = np.asarray(op.matvec(jnp.ones(op.n))).reshape(7, 6, 5)
    assert (sums[1:-1, 1:-1, 1:-1] == 0.0).all()
    for corner in itertools.product((0, -1), repeat=3):
        assert sums[corner] == 19.0
    # an edge point keeps 11 neighbours, a face point 17
    assert sums[0, 0, 2] == 26 - 11 and sums[0, 2, 2] == 26 - 17


def test_single_device_solve_matches_dense_solve(x64):
    op = Stencil27Operator.hpcg(16, 16, 8, dtype=jnp.float64)
    b = np.random.default_rng(1).standard_normal(op.n)
    want = np.linalg.solve(assembled(np.asarray(op.c), 16, 16, 8), b)
    res = repro.make_solver("p-bicgsafe-rr", op, config=SolverConfig(
        tol=1e-10, maxiter=500, rr_epoch=20)).solve(jnp.asarray(b))
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), want, rtol=1e-8,
                               atol=1e-8 * np.abs(want).max())


def test_mesh_solves_on_four_devices():
    """The subprocess checks halo matvec, sharded solve, loop structure
    and compiled collectives on three process grids, in under 90 s."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # the script sets its own
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_stencil27_mesh_check.py")],
        capture_output=True, text=True, env=env, timeout=600)
    took = time.perf_counter() - t0
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "STENCIL27 MESH CHECKS PASSED" in proc.stdout
    assert took < 90, f"the four-device checks took {took:.1f} s"


def test_mesh_preconditioners():
    """On a mesh the 27-point operator takes Jacobi, built from its
    block; any other name is refused with the operator named."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("x",))
    op = Stencil27Operator.hpcg(8, 8, 8)
    b = jnp.asarray(np.random.default_rng(4).standard_normal((8, 8, 8)),
                    jnp.float32)
    cfg = SolverConfig(tol=1e-4, maxiter=200, rr_epoch=20)
    res = repro.make_solver("p-bicgsafe-rr", op, precond="jacobi",
                            config=cfg).on_mesh(mesh).solve(b)
    assert bool(res.converged)
    with pytest.raises(ValueError, match="Stencil27Operator"):
        repro.make_solver("p-bicgsafe-rr", op, precond="block_jacobi",
                          config=cfg).on_mesh(mesh).solve(b)


def test_layout_and_split_checks():
    """b without a sharding takes x-slabs over every mesh axis; a grid
    axis that does not divide by its shards is a clear error."""
    from jax.sharding import AbstractMesh

    from repro.core import distributed

    mesh = AbstractMesh((2, 2, 1), ("x", "y", "z"))
    assert distributed.grid_layout(mesh, np.zeros((8, 8, 8))) == (
        ("x", "y", "z"), (), ())
    assert distributed.grid_layout(mesh, shard_axes=("y",)) == (
        ("y",), (), ())
    split, local = distributed.decompose(
        mesh, (("x",), ("y",), ("z",)), (8, 6, 5))
    assert local == (4, 3, 5)
    assert split == ((("x",), (2,)), (("y",), (2,)), ((), ()))
    with pytest.raises(ValueError, match="grid axis y has 7 points"):
        distributed.decompose(mesh, (("x",), ("y",), ()), (8, 7, 5))
