"""The 27-point configuration: its operator's three forms agree, the
reference imports nothing of the program and reads it in x-slabs, its
files load as a four-chip cell, and its solve entry on a 2 x 2 x 1
process grid of four host devices reads ``correct`` true when sound and
false for the bfloat16 control and without the halo exchange."""
import math
import sys

import numpy as np
import pytest

from chipbench import cells, control, reference, traffic
from chipbench.tests.small import run, small_cell

CELL = "hpcg-2x2x1-solve"
RNG = np.random.default_rng(27)
HPCG = cells.load_cell(CELL).config["operator"]
SMALL = dict(HPCG, grid=[9, 6, 5])
RANDOM = dict(SMALL, coeffs=RNG.uniform(-1.0, 1.0, (3, 3, 3)).tolist())
PLANE = SMALL["grid"][1] * SMALL["grid"][2]


def vectors(grid, seed=7):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(grid).astype(np.float32),
            rng.standard_normal(grid).astype(np.float32))


@pytest.mark.parametrize("cfg", [SMALL, RANDOM], ids=["hpcg", "random"])
def test_three_forms_are_one_operator(cfg):
    """The program's operator, the device check and the reference give
    the same A x, to float32 rounding."""
    import jax.numpy as jnp

    op = cells.operator("stencil27")
    _, x = vectors(cfg["grid"])
    want = op.apply_f64(op.params(cfg), x)
    dev = np.asarray(op.apply_f32(jnp.asarray(op.params(cfg), jnp.float32),
                                  jnp.asarray(x)))
    prog = op.build(cfg)
    assert prog.dtype == jnp.float32
    got = np.asarray(prog.matvec(jnp.asarray(x.ravel()))).reshape(x.shape)
    scale = np.abs(want).max()
    np.testing.assert_allclose(dev, want, atol=1e-5 * scale)
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)


def test_hpcg_coefficients():
    c = cells.operator("stencil27").params(HPCG)
    assert c[1, 1, 1] == 26.0
    assert (np.delete(c.ravel(), 13) == -1.0).all()


@pytest.mark.parametrize("slab_planes", [1, 2, 4, 5, 8, 9])
def test_slabbed_residual_is_the_whole_grids(slab_planes, monkeypatch):
    """Slabs of uneven thickness (9 planes in 4 + 4 + 1, ...) give the
    residual of the whole grid to 1e-12 relative."""
    monkeypatch.setattr(reference, "SLAB_UNKNOWNS", slab_planes * PLANE + 1)
    op = cells.operator("stencil27")
    b, x = vectors(RANDOM["grid"])
    whole = (np.linalg.norm(b - op.apply_f64(op.params(RANDOM), x))
             / np.linalg.norm(b.astype(np.float64)))
    got = reference.relative_residual(RANDOM, b.ravel(), x.ravel())
    assert abs(got - whole) <= 1e-12 * whole


def test_reference_imports_nothing_of_the_program(monkeypatch):
    for mod in [m for m in sys.modules if m == "repro"
                or m.startswith("repro.")]:
        monkeypatch.delitem(sys.modules, mod)
    monkeypatch.setitem(sys.modules, "repro", None)
    op = cells.operator("stencil27")
    b, x = vectors(SMALL["grid"])
    assert op.apply_f64(op.params(SMALL), x).dtype == np.float64
    assert math.isfinite(reference.relative_residual(SMALL, b, x))
    with pytest.raises(ImportError):
        op.build(SMALL)


def test_files_load_as_a_four_chip_cell():
    cell = cells.load_cell(CELL)
    assert cell.chips == cells.chips(cell.config) == 4
    assert cell.config["process_grid"] == [2, 2, 1]
    assert cell.config["operator"]["kind"] == "stencil27"
    assert cell.traffic == traffic.load(
        cells.HERE / "traffic" / "solve-closed1-set8.json")
    assert cell.traffic["rhs_set"] == 8
    layer = {m["name"] for m in cell.per_layer}
    assert {"exposed_collective_frac.solve",
            "halo_bytes_per_matvec.solve"} <= layer


@pytest.fixture
def fresh_sessions():
    """Sessions are cached by content: every run builds its programs
    anew, so that a planted fault reaches them."""
    from repro import api
    api.clear_session_cache()
    yield
    api.clear_session_cache()


def test_sound_run_is_correct(fresh_sessions):
    cell = small_cell(CELL)
    out = run(cell)
    assert out.correct, out.checks
    assert out.attempted > 0 and out.failed == 0
    assert (out.lowered_in_window, out.compiled_in_window) == (0, 0)
    nx, ny, nz = cell.config["operator"]["grid"]
    # one plane to the one neighbour along x and along y (the y plane
    # is the x-extended block's)
    want = ((ny // 2) * nz + (nx // 2 + 2) * nz) * 4
    assert cells.metric_reader("halo_bytes_per_matvec.solve")(
        out.record) == want


def test_control_reads_not_correct(fresh_sessions):
    out = run(control.as_control(small_cell(CELL)))
    assert not out.correct, out.checks


def test_no_exchange_reads_not_correct(fresh_sessions, monkeypatch):
    import jax.numpy as jnp

    from repro.core import distributed

    monkeypatch.setattr(distributed, "ring_shift",
                        lambda x, *a, **k: jnp.zeros_like(x))
    out = run(small_cell(CELL))
    assert not out.correct, out.checks
