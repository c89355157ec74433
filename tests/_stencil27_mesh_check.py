"""Sharded 27-point checks — run in a subprocess with 4 host devices.

Invoked by tests/test_stencil27.py.  On process grids 2x2x1, 4x1x1 and
1x2x2, with the right-hand side laid out by ``P("x", "y", "z")``: the
halo matvec, in the box form HPCG's coefficients take, equals the
one-device matvec in either form, the sharded solve meets its
tol by its true residual in about the one-device iterations and returns
x in b's blocks, the loop body holds one psum with no dependency path to
the halo permutes, the halo-bytes gauge is the shapes' count, and the
2x2x1 program compiles with no all-to-all and no all-gather.  Exits
nonzero on any failure.
"""
import functools
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import repro  # noqa: E402
from repro import SolverConfig, Stencil27Operator  # noqa: E402
from repro.analysis import eqn_needs_ppermute, find_while_body  # noqa: E402
from repro.core import distributed  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.observe import metrics  # noqa: E402

GRID = (16, 16, 8)
TOL = 1e-8
SPEC = P("x", "y", "z")


def check_grid(shape, op, b, ref_iters):
    mesh = make_mesh(shape, ("x", "y", "z"))
    sharding = NamedSharding(mesh, SPEC)
    b_grid = jax.device_put(b.reshape(GRID), sharding)
    layout = distributed.grid_layout(mesh, b_grid)
    assert layout == (("x",), ("y",), ("z",)), layout
    split, local = distributed.decompose(mesh, layout, GRID)

    # the halo matvec is the one-device matvec
    local_mv = functools.partial(distributed.halo_matvec,
                                 type(op)(op.c, *local), split)
    mv = jax.jit(jax.shard_map(
        lambda v: local_mv(v.reshape(-1)).reshape(v.shape),
        mesh=mesh, in_specs=(SPEC,), out_specs=SPEC, check_vma=False))
    u = jax.device_put(jnp.asarray(np.random.default_rng(5).standard_normal(
        GRID)), sharding)
    want = np.asarray(op.matvec(jnp.asarray(u).reshape(-1))).reshape(GRID)
    boxes = metrics.STENCIL_FORM.value(operator="stencil27", form="box")
    got = np.asarray(mv(u.reshape(GRID)))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-6, (shape, err)

    # ... in the box form, against the one-device 27-term form (the
    # operator passed into jit as an argument traces its c)
    assert metrics.STENCIL_FORM.value(
        operator="stencil27", form="box") == boxes + 1, shape
    general = np.asarray(jax.jit(lambda o, v: o.matvec(v))(
        op, jnp.asarray(u).reshape(-1))).reshape(GRID)
    err = np.abs(got - general).max() / np.abs(general).max()
    assert err < 1e-6, (shape, err)

    # the sharded solve: true residual, iterations, x in b's blocks
    dist = repro.make_solver("p-bicgsafe-rr", op, config=SolverConfig(
        tol=TOL, maxiter=2000, rr_epoch=20)).on_mesh(mesh)
    res = dist.solve(b_grid)
    assert res.x.sharding.spec == SPEC, res.x.sharding
    x = jnp.asarray(res.x).reshape(-1)
    true = float(jnp.linalg.norm(b - op.matvec(x)) / jnp.linalg.norm(b))
    assert bool(res.converged) and true <= TOL * 1.001, (shape, true)
    its = int(res.iterations)
    assert abs(its - ref_iters) <= max(3, int(0.1 * ref_iters)), \
        (shape, its, ref_iters)

    # the gauge: one plane to each neighbour along each split axis
    halo = {v["labels"]["axis"]: v["value"] for v in
            metrics.snapshot()["repro_mesh_halo_bytes"]["values"]}
    ext, want_bytes = list(local), {}
    for d, (axis, s) in enumerate(zip("xyz", shape)):
        planes = min(2, s - 1)
        want_bytes[axis] = planes * (np.prod(ext) // ext[d]) * 8
        ext[d] += 2
    assert halo == want_bytes, (shape, halo, want_bytes)

    # the loop body: ONE psum, no dependency path to the halo permutes
    fn = distributed.build_stencil_solver(
        repro.SOLVERS["p-bicgsafe-rr"], op, mesh, layout=layout,
        config=SolverConfig(maxiter=10, rr_epoch=20), jit=False)
    body = find_while_body(jax.make_jaxpr(fn)(b_grid).jaxpr)
    psums = [e for e in body.eqns if e.primitive.name == "psum"]
    assert len(psums) == 1, (shape, len(psums))
    permutes, needs = eqn_needs_ppermute(body, psums[0])
    assert permutes and not needs, (shape, len(permutes), needs)

    # the compiled program takes b's blocks as they are
    text = jax.jit(fn).lower(b_grid).compile().as_text()
    for op_name in ("all-to-all", "all-gather"):
        assert op_name not in text, (shape, op_name)
    print(f"  ok grid={shape} iters={its} (one device {ref_iters}) "
          f"true relres={true:.2e} halo bytes={halo}")


def check_solve_many(op, b):
    """solve_many lays B out in x-slabs over the flattened mesh."""
    mesh = make_mesh((2, 2), ("a", "b"))
    B = jnp.stack([b, -2.0 * b[::-1]], axis=1)
    res = repro.make_solver("p-bicgsafe", op, config=SolverConfig(
        tol=TOL, maxiter=2000)).on_mesh(mesh).solve_many(
            B.reshape(*GRID, 2))
    assert bool(np.asarray(res.converged).all())
    X = jnp.asarray(res.x).reshape(-1, 2)
    for j in range(2):
        true = float(jnp.linalg.norm(B[:, j] - op.matvec(X[:, j]))
                     / jnp.linalg.norm(B[:, j]))
        assert true <= 1e-6, (j, true)
    print(f"  ok solve_many iters={np.asarray(res.iterations)}")


def main():
    assert jax.device_count() == 4, jax.device_count()
    op = Stencil27Operator.hpcg(*GRID, dtype=jnp.float64)
    b = jnp.asarray(np.random.default_rng(2).standard_normal(op.n))
    ref = repro.make_solver("p-bicgsafe-rr", op, config=SolverConfig(
        tol=TOL, maxiter=2000, rr_epoch=20)).solve(b)
    for shape in ((2, 2, 1), (4, 1, 1), (1, 2, 2)):
        check_grid(shape, op, b, int(ref.iterations))
    check_solve_many(op, b)
    print("STENCIL27 MESH CHECKS PASSED")


if __name__ == "__main__":
    main()
