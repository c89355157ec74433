"""A configuration's process grid on four host devices: [4, 1, 1] is the
program's x-slab layout, whose answers and iterations are those of a 1-D
mesh of four rows, and [2, 2, 1] lays the right-hand sides out in 2 x 2
blocks and still reads ``correct``; neither compiles in the window."""
import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from chipbench import harness, traffic
from chipbench.tests.small import MESHES, cell_for, run

SEED = 2 ** 33 + 5


def rows_path(cell):
    """``solve(b) -> (x, iterations)`` on a 1-D mesh of four rows, and
    its right-hand sides."""
    cfg = cell.config
    mesh = jax.make_mesh((4,), ("rows",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:4])
    dist = harness._session(cfg, float(cell.traffic["tols"][0])).on_mesh(
        mesh)
    make_b = traffic.make_rhs_fn(tuple(cfg["operator"]["grid"]),
                                 cfg["operator"]["dtype"],
                                 NamedSharding(mesh, P("rows")))

    def solve(b):
        res = dist.solve(b)
        return np.asarray(res.x), int(res.iterations)
    return solve, make_b


def test_4x1x1_is_the_rows_layout():
    cell = cell_for("mesh")
    cfg = cell.config
    solve_rows, b_rows = rows_path(cell)
    mesh = harness._mesh(jax.devices(), MESHES["mesh"])
    dist = harness._session(cfg, float(cell.traffic["tols"][0])).on_mesh(
        mesh)
    b_grid = traffic.make_rhs_fn(tuple(cfg["operator"]["grid"]),
                                 cfg["operator"]["dtype"],
                                 NamedSharding(mesh, P("x", "y", "z")))
    for j in (0, 5):
        want_x, want_its = solve_rows(b_rows(j, -2.0))
        res = dist.solve(b_grid(j, -2.0))
        assert int(res.iterations) == want_its
        np.testing.assert_array_equal(np.asarray(res.x), want_x)

    # the harness's window on [4, 1, 1]: every request that needed no
    # refinement took the iterations the rows layout takes
    out = run(cell, seed=SEED)
    assert out.correct, out.checks
    assert (out.lowered_in_window, out.compiled_in_window) == (0, 0)
    stream = traffic.requests(cell.traffic, SEED)
    compared = 0
    for rec in out.record.solves[:4]:
        req = next(stream)
        if rec.refines == 0:
            assert rec.iterations == solve_rows(b_rows(req.rhs,
                                                       req.scale))[1]
            compared += 1
    assert compared


def test_2x2x1_blocks(monkeypatch):
    cell = cell_for("mesh2x2")
    nx, ny, nz = cell.config["operator"]["grid"]
    shards = set()
    real = traffic.make_rhs_fn

    def recording(*a, **k):
        f = real(*a, **k)

        def g(*args):
            b = f(*args)
            shards.update(s.data.shape for s in b.addressable_shards)
            return b
        return g
    monkeypatch.setattr(traffic, "make_rhs_fn", recording)
    out = run(cell, seed=SEED + 1)
    assert out.correct, out.checks
    assert out.attempted > 0
    assert (out.lowered_in_window, out.compiled_in_window) == (0, 0)
    assert shards == {(nx // 2, ny // 2, nz)}
