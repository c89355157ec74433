"""Operators found by the configuration's kind, and the float64 reference
built from them: its three forms agree, the reference imports nothing of
the program, and the residual taken in x-slabs is the whole grid's."""
import json
import math
import sys

import numpy as np
import pytest

from chipbench import cells, reference

BENCH = cells.load_benchmark()
SMALL = {"kind": "stencil7", "grid": [11, 6, 5], "dtype": "float32",
         "coeffs": [6.75, -1.5, -1.0, -1.25, -1.0, -1.0, -1.0]}


def config(name: str) -> dict:
    c = next(c for c in BENCH["configs"] if c["name"] == name)
    return json.loads((cells.ROOT / c["file"]).read_text())


def vectors(grid, seed=7):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(grid).astype(np.float32),
            rng.standard_normal(grid).astype(np.float32))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_kind_resolves(name):
    o = config(name)["operator"]
    op = cells.operator(o["kind"])
    for f in ("params", "build", "apply_f32", "apply_f64"):
        assert callable(getattr(op, f))
    assert len(config(name)["process_grid"]) == len(o["grid"]) == 3


def test_unknown_kind_names_the_missing_file():
    with pytest.raises(KeyError, match=r"operators/no_such_kind\.py"):
        cells.operator("no_such_kind")


def test_reference_imports_nothing_of_the_program(monkeypatch):
    """With the program's package blocked, the operator module loads,
    its float64 form and the reference run, and only ``build`` fails."""
    for mod in [m for m in sys.modules if m == "repro"
                or m.startswith("repro.")]:
        monkeypatch.delitem(sys.modules, mod)
    monkeypatch.setitem(sys.modules, "repro", None)
    op = cells.operator("stencil7")
    b, x = vectors(SMALL["grid"])
    assert op.apply_f64(op.params(SMALL), x).dtype == np.float64
    assert math.isfinite(reference.relative_residual(SMALL, b, x))
    with pytest.raises(ImportError):
        op.build(SMALL)


def test_three_forms_are_one_operator():
    """The program's operator, the device check and the reference give
    the same A x, to float32 rounding."""
    import jax.numpy as jnp

    op = cells.operator("stencil7")
    _, x = vectors(SMALL["grid"])
    want = op.apply_f64(op.params(SMALL), x)
    dev = np.asarray(op.apply_f32(jnp.asarray(op.params(SMALL), jnp.float32),
                                  jnp.asarray(x)))
    prog = op.build(SMALL)
    assert prog.dtype == jnp.float32
    got = np.asarray(prog.matvec(jnp.asarray(x.ravel()))).reshape(x.shape)
    scale = np.abs(want).max()
    np.testing.assert_allclose(dev, want, atol=1e-5 * scale)
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)
    assert op.build(dict(SMALL, dtype="bfloat16")).dtype == jnp.bfloat16


PLANE = SMALL["grid"][1] * SMALL["grid"][2]


@pytest.mark.parametrize("slab_planes", [1, 3, 4, 7, 10, 11])
def test_slabbed_residual_is_the_whole_grids(slab_planes, monkeypatch):
    """Slabs of uneven thickness (11 planes in 3 + 3 + 3 + 2, ...) give
    the residual of the whole grid to 1e-12 relative."""
    monkeypatch.setattr(reference, "SLAB_UNKNOWNS", slab_planes * PLANE + 1)
    op = cells.operator("stencil7")
    b, x = vectors(SMALL["grid"])
    whole = (np.linalg.norm(b - op.apply_f64(op.params(SMALL), x))
             / np.linalg.norm(b.astype(np.float64)))
    got = reference.relative_residual(SMALL, b.ravel(), x.ravel())
    assert abs(got - whole) <= 1e-12 * whole


def test_non_finite_answer_is_infinitely_wrong(monkeypatch):
    monkeypatch.setattr(reference, "SLAB_UNKNOWNS", 3 * PLANE)
    b, x = vectors(SMALL["grid"])
    x[9, 2, 3] = np.nan
    assert reference.relative_residual(SMALL, b, x) == float("inf")
