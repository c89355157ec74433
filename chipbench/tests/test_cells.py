"""Each cell's parts are found by name, and each file is what the
benchmark's contract asks of it."""
import json
import re

import pytest

from chipbench import cells, traffic

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(workload):
    cell = cells.load_cell(workload)
    assert cell.config["name"] == next(
        w for w in BENCH["workloads"] if w["name"] == workload)["config"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in e2e
    assert len(cell.config["process_grid"]) == 3
    assert cells.chips(cell.config) == cell.chips


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))
    with pytest.raises(KeyError):
        cells.metric_reader("no_such_metric")


def test_names_and_files():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        data = json.loads((cells.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        traffic.load(cells.HERE / "traffic" / f"{w['traffic']}.json")


def test_unknown_workload():
    with pytest.raises(KeyError):
        cells.load_cell("no-such-cell")


def test_configured_operator_is_the_generator():
    """The coefficients the configuration states are those of the
    program's convection-diffusion generator."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import matrices

    cd = json.loads((cells.HERE / "configs" / "atmosmodd-cd7.json")
                    .read_text())
    op, _, _ = matrices.convection_diffusion(4, peclet=0.5,
                                             dtype=jnp.float32)
    np.testing.assert_allclose(cd["operator"]["coeffs"], np.asarray(op.c))


def test_grid_is_the_source_matrix():
    """The configured 7-point grid has the source matrix's rows and
    entries: every point couples to itself and its in-grid neighbours."""
    import numpy as np

    cd = json.loads((cells.HERE / "configs" / "atmosmodd-cd7.json")
                    .read_text())
    grid = cd["operator"]["grid"]
    assert int(np.prod(grid)) == cd["source_rows"]
    neighbours = sum(int(np.prod(grid)) // g * (g - 1) * 2 for g in grid)
    assert int(np.prod(grid)) + neighbours == cd["source_entries"]


def test_traffic_stream_is_the_seeds():
    mix = traffic.load(cells.HERE / "traffic" / "serve-closed16.json")
    big = 2 ** 33 + 11
    kinds = mix["rhs_set"] * len(mix["tols"])

    def first(seed, n=kinds):
        it = traffic.requests(mix, seed)
        return [next(it) for _ in range(n)]
    assert first(big) == first(big)
    assert first(big) != first(big + 1)
    # every seed asks for each (base, tol) pair once a pass
    for seed in (big, big + 1):
        reqs = first(seed, 3 * kinds)
        for p in range(3):
            one_pass = reqs[p * kinds:(p + 1) * kinds]
            assert sorted((r.rhs, r.tol) for r in one_pass) == sorted(
                (j, t) for j in range(mix["rhs_set"]) for t in mix["tols"])
        assert {abs(r.scale) for r in reqs} <= {
            2.0 ** e for e in range(-traffic.MAX_EXP, traffic.MAX_EXP + 1)}
        assert {r.scale > 0 for r in reqs} == {True, False}


def test_mixes_hold_only_their_keys(tmp_path):
    for name in ("solve-closed1", "serve-closed16"):
        mix = traffic.load(cells.HERE / "traffic" / f"{name}.json")
        path = tmp_path / f"{name}.json"
        path.write_text(json.dumps(dict(mix, extra=1)))
        with pytest.raises(ValueError):
            traffic.load(path)


def test_scaled_right_hand_sides_solve_alike():
    """A sign and a power of two on b change no iteration: the work of a
    request depends on its base vector alone."""
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.core import SolverConfig, Stencil7Operator

    f = traffic.make_rhs_fn((16 * 12 * 8,), "float32")
    np.testing.assert_array_equal(f(3, 1.0), f(3, 1.0))
    assert not np.array_equal(f(3, 1.0), f(4, 1.0))
    np.testing.assert_array_equal(f(np.arange(5), 1.0)[3], f(3, 1.0))
    np.testing.assert_array_equal(f(3, -8.0), -8.0 * np.asarray(f(3, 1.0)))
    op = Stencil7Operator(jnp.asarray([6.75, -1.5, -1.0, -1.25, -1.0, -1.0,
                                       -1.0], jnp.float32), 16, 12, 8)
    s = repro.make_solver("p-bicgsafe-rr", op, config=SolverConfig(
        tol=1e-4, maxiter=500, rr_epoch=20))
    one, scaled = s.solve(f(3, 1.0)), s.solve(f(3, -0.0625))
    assert int(one.iterations) == int(scaled.iterations)
    np.testing.assert_array_equal(np.asarray(scaled.x),
                                  -0.0625 * np.asarray(one.x))
