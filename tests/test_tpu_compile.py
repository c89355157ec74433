"""Compile the main path's Pallas kernels, the pallas p-BiCGSafe solve
program and the 27-point stencil's two forms for a described (not
attached) TPU v5e, at 256^3 in float32.

Interpret mode, which every other test runs the kernels in, does not
check what Mosaic refuses: block shapes off the (8, 128) tiling, in-kernel
gathers, 64-bit types.  These compiles do, with no chip.  The topology is
described inside a module fixture, never at import: only the worker that
runs this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import enable_x64

N = 256 ** 3
M = 8
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_cases():
    from repro.kernels import fused_axpy as FA
    from repro.kernels import fused_dots as FD
    from repro.kernels import precond_apply as PA
    from repro.kernels import spmv_ell as SE

    offsets = (-129, -2, -1, 0, 1, 2, 129)     # a banded 7-diagonal matrix
    rows = N // 128
    v, B = (N,), (N, M)
    return {
        "fused_dots": (lambda *a: FD.fused_dots_pallas(*a), [v] * 5),
        "fused_dots_health": (lambda *a: FD.fused_dots_health_pallas(*a),
                              [v] * 6),
        "fused_dots_batched": (lambda *a: FD.fused_dots_batched_pallas(*a),
                               [B] * 5),
        "fused_dots_health_batched": (
            lambda *a: FD.fused_dots_health_batched_pallas(*a), [B] * 6),
        "fused_axpy": (
            lambda *a: FA.fused_axpy_pallas(dict(zip(FA.IN_ORDER, a[:12])),
                                            a[12:]),
            [v] * 12 + [()] * 4),
        "fused_axpy_batched": (
            lambda *a: FA.fused_axpy_batched_pallas(
                dict(zip(FA.IN_ORDER, a[:12])), a[12:16], a[16] > 0),
            [B] * 12 + [(M,)] * 5),
        "spmv_ell": (
            lambda d, x: SE._spmv_dia(d, x[:, None], offsets=offsets,
                                      block_rows=64, interpret=False),
            [(len(offsets), rows, 128), v]),
        "spmv_ell_batched": (
            lambda d, x: SE._spmv_dia(d, x, offsets=offsets, block_rows=64,
                                      interpret=False),
            [(len(offsets), rows, 128), B]),
        # 64: the default block size of a general operator's block-Jacobi
        "block_jacobi": (lambda ib, x: PA.block_jacobi_apply_pallas(ib, x),
                         [(N // 64, 64, 64), v]),
    }


@pytest.mark.parametrize("name", [
    "fused_dots", "fused_dots_health", "fused_dots_batched",
    "fused_dots_health_batched", "fused_axpy", "fused_axpy_batched",
    "spmv_ell", "spmv_ell_batched", "block_jacobi"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert CUSTOM_CALL in text, f"{name}: no Mosaic kernel in the program"


def test_pallas_solve_program_compiles_for_v5e(one_chip, monkeypatch):
    """The whole session solve program, kernels compiled (not
    interpreted), at the size chip_smoke.py solves."""
    from repro.api import LinearSolver
    from repro.core import SolverConfig
    from repro.core.linear_operator import Stencil7Operator
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    c = jnp.array([6.75, -1.5, -1.0, -1.25, -1.0, -1.0, -1.0], jnp.float32)
    op = Stencil7Operator(c, 256, 256, 256)
    solver = LinearSolver("p-bicgsafe-rr", op, substrate="pallas",
                          config=SolverConfig(tol=1e-5, maxiter=100))
    b = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda b: solver.solve(b).x).lower(b).compile()
    assert compiled.as_text().count(CUSTOM_CALL) >= 2   # dots + axpy


def _estimated_cycles(fn, *args):
    """The compiler's estimated cycles, summed over the program's ops."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return sum(int(c) for c in re.findall(r'"estimated_cycles":"(\d+)"',
                                          text))


def test_stencil27_box_form_is_cheaper_for_v5e(one_chip):
    """HPCG's 27-point apply on a padded 256^3 block: the box form (three
    3-point sums) is estimated at most 0.6x the cycles of the 27-term
    form, which the same coefficients take when passed as an argument."""
    from repro.core.linear_operator import Stencil27Operator

    p = jax.ShapeDtypeStruct((258, 258, 258), jnp.float32,
                             sharding=one_chip)
    op = Stencil27Operator.hpcg(256, 256, 256)
    traced = Stencil27Operator(
        jax.ShapeDtypeStruct((3, 3, 3), jnp.float32, sharding=one_chip),
        256, 256, 256)
    box = _estimated_cycles(op.apply_padded, p)
    general = _estimated_cycles(lambda o, v: o.apply_padded(v), traced, p)
    assert 0 < box <= 0.6 * general, (box, general)


def test_pallas_refuses_float64_at_bind_on_tpu(monkeypatch):
    """Mosaic kernels are 32-bit: a float64 operator bound to the pallas
    substrate on a TPU backend fails at bind with one line, not deep in
    the compiler."""
    import repro
    from repro.core import matrices
    from repro.kernels import ops

    with enable_x64(True):
        op, _, _ = matrices.convection_diffusion(4, dtype=jnp.float64)
        monkeypatch.setattr(ops, "_interpret", lambda: False)
        with pytest.raises(ValueError, match="float64"):
            repro.make_solver("p-bicgsafe", op, substrate="pallas")
        monkeypatch.undo()
        assert repro.make_solver("p-bicgsafe", op,
                                 substrate="pallas").kernel_backed
