"""The least bytes an iteration moves, against hand-counted shapes and
against what the compiled solve program says it moves."""
import numpy as np
import pytest

from chipbench.floor import floor_bytes


def test_hand_counted():
    # 11 vectors read and written, r0* read: 23 passes of 4-byte words
    assert floor_bytes((128, 128, 128), "float32", "p-bicgsafe-rr") == \
        23 * 128 ** 3 * 4
    # a quarter of a 512x256x256 grid per chip
    assert floor_bytes((512, 256, 256), np.float32, "p-bicgsafe-rr", 4) \
        == 23 * 128 * 256 * 256 * 4
    assert floor_bytes((8, 8, 8), "bfloat16", "p-bicgsafe") == 23 * 512 * 2


def test_refuses_what_it_cannot_count():
    with pytest.raises(KeyError):
        floor_bytes((8, 8, 8), "float32", "bicgstab")
    with pytest.raises(ValueError):
        floor_bytes((3, 3, 3), "float32", "p-bicgsafe-rr", 4)


def test_below_the_compiled_program():
    """XLA's cost analysis of the solve program (its loop body counted
    once, with its set-up) moves more bytes than one iteration's floor:
    the floor can only under-count."""
    import jax.numpy as jnp

    import repro
    from repro.core import SolverConfig, Stencil7Operator

    grid = (16, 16, 16)
    op = Stencil7Operator(jnp.asarray([6.75, -1.5, -1.0, -1.25, -1.0,
                                       -1.0, -1.0], jnp.float32), *grid)
    s = repro.make_solver("p-bicgsafe-rr", op,
                          config=SolverConfig(tol=1e-4, maxiter=10,
                                              rr_epoch=20))
    b = jnp.ones((op.n,), jnp.float32)
    s.solve(b)
    (prog,) = [fn for key, fn in s._programs.items() if key[0] == "solve"]
    cost = prog.lower(b, None, None).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert floor_bytes(grid, "float32", "p-bicgsafe-rr") < \
        cost["bytes accessed"]
