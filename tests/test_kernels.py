"""Pallas kernels vs. pure-jnp oracles (interpret mode), sweeping
shapes and dtypes as required for each kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import enable_x64
from repro.core.linear_operator import ELLOperator
from repro.core import matrices as M
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_axpy import (IN_ORDER, fused_axpy_batched_pallas,
                                      fused_axpy_pallas)
from repro.kernels.fused_dots import (fused_dots_batched_pallas,
                                      fused_dots_pallas)
from repro.kernels.spmv_ell import (spmv_ell_batched_pallas,
                                    spmv_ell_pallas)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("n", [100, 4096, 40_000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_dots(n, dtype):
    with enable_x64(dtype == jnp.float64):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        vecs = [rand(k, (n,), dtype) for k in ks]
        got = fused_dots_pallas(*vecs, interpret=True)
        want = ref.fused_dots(*vecs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5)


@pytest.mark.parametrize("n,m", [(100, 1), (1000, 7), (4096, 32),
                                 (513, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_dots_batched(n, m, dtype):
    """Multi-RHS kernel: (n, m) blocks -> (9, m) partials, incl. lane
    padding (m=7, 130) and row-block padding (n=513)."""
    with enable_x64(dtype == jnp.float64):
        ks = jax.random.split(jax.random.PRNGKey(5), 5)
        vecs = [rand(k, (n, m), dtype) for k in ks]
        got = fused_dots_batched_pallas(*vecs, interpret=True)
        want = ref.fused_dots_batched(*vecs)
        assert got.shape == (9, m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=1e-5)
        # column j of the batched kernel == the 1-D kernel on column j
        col = [v[:, 0] for v in vecs]
        np.testing.assert_allclose(
            np.asarray(got[:, 0]),
            np.asarray(fused_dots_pallas(*col, interpret=True)),
            rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("n,stencil", [(512, True), (4096, True),
                                       (1000, False)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_spmv_ell(n, stencil, dtype):
    with enable_x64(dtype == jnp.float64):
        if stencil:
            # banded matrix: tridiagonal-ish with k=5
            rng = np.random.default_rng(0)
            k = 5
            offs = np.array([-2, -1, 0, 1, 2])
            cols = np.clip(np.arange(n)[:, None] + offs[None, :], 0, n - 1)
            vals = rng.standard_normal((n, k))
            vals[cols == np.arange(n)[:, None]] += 3.0
            op = ELLOperator(jnp.asarray(vals, dtype),
                             jnp.asarray(cols, np.int32), n)
        else:
            csr, _, _ = M.random_nonsym(n, 6, seed=1, dtype=np.float64)
            op = ELLOperator.from_csr(csr)
            op = ELLOperator(op.values.astype(dtype), op.cols, n)
            pytest.skip("non-banded: ops.spmv_ell falls back to jnp ref")
        x = rand(jax.random.PRNGKey(2), (n,), dtype)
        got = spmv_ell_pallas(op.values, op.cols, x, interpret=True)
        want = ref.spmv_ell(op.values, op.cols, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_spmv_ell_shifted_diagonals_cross_tiles(dtype):
    """Diagonals that shift across lanes AND (rows, 128) tiles, with
    unit-lane, lane-crossing and block-crossing offsets; single and
    block kernels vs the gather oracle."""
    n = 20000
    offs = np.array([-1000, -129, -1, 0, 1, 128, 300])
    rows = np.arange(n)[:, None]
    cols = rows + offs[None, :]
    out = (cols < 0) | (cols >= n)
    cols = np.where(out, rows, cols).astype(np.int32)
    with enable_x64(dtype == jnp.float64):
        rng = np.random.default_rng(4)
        vals = rng.standard_normal((n, len(offs)))
        vals[out] = 0.0                  # ELL padding slots
        values = jnp.asarray(vals, dtype)
        x = rand(jax.random.PRNGKey(3), (n, 3), dtype)
        want = ref.spmv_ell(values, jnp.asarray(cols), x)
        got = spmv_ell_batched_pallas(values, cols, x, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        got1 = spmv_ell_pallas(values, cols, x[:, 1], interpret=True)
        np.testing.assert_allclose(np.asarray(got1), np.asarray(want[:, 1]),
                                   rtol=1e-4, atol=1e-5)


def test_ell_dispatch_needs_few_diagonals_in_band():
    """The kernel path serves banded ELL on a bounded number of
    diagonals; a matrix with more (or a wider band) keeps the gather."""
    from repro.kernels import ops
    n = 600
    rows = np.arange(n)[:, None]
    narrow = np.clip(rows + np.array([-2, -1, 0, 1, 2]), 0, n - 1)
    op = ELLOperator(jnp.ones((n, 5)), jnp.asarray(narrow, np.int32), n)
    assert ops.ell_is_banded(op)
    rng = np.random.default_rng(0)
    scattered = np.clip(rows + rng.integers(-200, 200, (n, 5)), 0, n - 1)
    op = ELLOperator(jnp.ones((n, 5)), jnp.asarray(scattered, np.int32), n)
    assert not ops.ell_is_banded(op)        # hundreds of diagonals


@pytest.mark.parametrize("n", [100, 8192])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_axpy(n, dtype):
    with enable_x64(dtype == jnp.float64):
        keys = jax.random.split(jax.random.PRNGKey(1), len(IN_ORDER))
        vecs = {k: rand(kk, (n,), dtype) for k, kk in zip(IN_ORDER, keys)}
        scalars = (0.3, -0.7, 1.1, 0.2)
        got = fused_axpy_pallas(vecs, scalars, interpret=True)
        want = ref.fused_axpy(vecs, scalars)
        for k in got:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]),
                rtol=5e-5, atol=1e-5, err_msg=k)


@pytest.mark.parametrize("n,m", [(100, 1), (1000, 7), (513, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_axpy_batched(n, m, dtype):
    """Multi-RHS update-phase kernel: (n, m) blocks with per-column
    coefficients, incl. lane padding (m=7, 130) and row padding (n=513)."""
    with enable_x64(dtype == jnp.float64):
        keys = jax.random.split(jax.random.PRNGKey(1), len(IN_ORDER) + 4)
        vecs = {k: rand(kk, (n, m), dtype)
                for k, kk in zip(IN_ORDER, keys)}
        scalars = tuple(rand(kk, (m,), dtype)
                        for kk in keys[len(IN_ORDER):])
        got = fused_axpy_batched_pallas(vecs, scalars, interpret=True)
        want = ref.fused_axpy(vecs, scalars)
        for k in got:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]),
                rtol=5e-5, atol=1e-5, err_msg=k)
        # column 0 of the batched kernel == the 1-D kernel on column 0
        col = {k: v[:, 0] for k, v in vecs.items()}
        got0 = fused_axpy_pallas(col, tuple(s[0] for s in scalars),
                                 interpret=True)
        for k in got0:
            np.testing.assert_allclose(
                np.asarray(got[k][:, 0]), np.asarray(got0[k]),
                rtol=5e-5, atol=1e-5, err_msg=k)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_axpy_batched_mask_freezes_columns(dtype):
    """The in-kernel convergence mask: frozen columns return their INPUT
    tiles bitwise for every state output; o/q stay fresh."""
    from repro.kernels.fused_axpy import MASKED_OUT
    with enable_x64(dtype == jnp.float64):
        n, m = 300, 5
        keys = jax.random.split(jax.random.PRNGKey(2), len(IN_ORDER) + 4)
        vecs = {k: rand(kk, (n, m), dtype)
                for k, kk in zip(IN_ORDER, keys)}
        scalars = tuple(rand(kk, (m,), dtype)
                        for kk in keys[len(IN_ORDER):])
        mask = jnp.asarray([True, False, True, False, False])
        got = fused_axpy_batched_pallas(vecs, scalars, mask, interpret=True)
        want = ref.fused_axpy(vecs, scalars, mask=mask)
        frozen = ~np.asarray(mask)
        for k in got:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]),
                rtol=5e-5, atol=1e-5, err_msg=k)
            if k in MASKED_OUT:
                np.testing.assert_array_equal(
                    np.asarray(got[k])[:, frozen],
                    np.asarray(vecs[k])[:, frozen], err_msg=k)


@pytest.mark.parametrize("n,m", [(512, 1), (1030, 4), (4096, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_spmv_ell_batched(n, m, dtype):
    """Block banded ELL SpMV: matrix tiles amortized over m columns."""
    with enable_x64(dtype == jnp.float64):
        rng = np.random.default_rng(0)
        k = 5
        offs = np.array([-2, -1, 0, 1, 2])
        cols = np.clip(np.arange(n)[:, None] + offs[None, :], 0, n - 1)
        vals = rng.standard_normal((n, k))
        vals[cols == np.arange(n)[:, None]] += 3.0
        values = jnp.asarray(vals, dtype)
        cols = jnp.asarray(cols, np.int32)
        x = rand(jax.random.PRNGKey(2), (n, m), dtype)
        got = spmv_ell_batched_pallas(values, cols, x, interpret=True)
        want = ref.spmv_ell(values, cols, x)
        assert got.shape == (n, m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        # column j == the 1-D kernel on column j
        col0 = spmv_ell_pallas(values, cols, x[:, 0], interpret=True)
        np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(col0),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [
    (1, 4, 4, 256, 64),     # MHA
    (2, 8, 2, 512, 64),     # GQA G=4
    (1, 2, 1, 1024, 128),   # MQA-ish, longer S
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(shape, dtype):
    B, H, K, S, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (B, H, S, hd), dtype)
    k = rand(ks[1], (B, K, S, hd), dtype)
    v = rand(ks[2], (B, K, S, hd), dtype)
    scale = 1.0 / np.sqrt(hd)
    got = flash_attention_pallas(q, k, v, scale=scale, causal=True,
                                 block_q=128, block_k=128, interpret=True)
    want = ref.flash_attention(q, k, v, scale=scale, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_non_causal():
    B, H, K, S, hd = 1, 4, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (rand(kk, (B, H if i == 0 else K, S, hd), jnp.float32)
               for i, kk in enumerate(ks))
    scale = 1.0 / np.sqrt(hd)
    got = flash_attention_pallas(q, k, v, scale=scale, causal=False,
                                 block_q=128, block_k=128, interpret=True)
    want = ref.flash_attention(q, k, v, scale=scale, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_solver_with_pallas_kernels():
    """End-to-end: p-BiCGSafe using the Pallas SpMV + fused dots
    (interpret) reproduces the jnp solver on a banded system."""
    import functools
    from repro.core import SolverConfig, pbicgsafe_solve
    from repro.kernels import ops

    with enable_x64(True):
        op, b, xt = M.poisson3d(8)   # stencil -> banded under natural order?
        # use a 1-D banded operator instead (guaranteed band)
        n = 2048
        rng = np.random.default_rng(0)
        offs = np.array([-2, -1, 0, 1, 2])
        cols = np.clip(np.arange(n)[:, None] + offs[None, :], 0, n - 1)
        vals = rng.standard_normal((n, 5))
        # strict row diagonal dominance -> guaranteed convergence
        vals[:, 2] = 1.0 + 1.2 * np.abs(vals).sum(axis=1)
        ell = ELLOperator(jnp.asarray(vals), jnp.asarray(cols, np.int32), n)
        xt = jnp.ones((n,), jnp.float64)
        b = ell.matvec(xt)

        mv = functools.partial(ops.spmv_ell, ell)
        res = pbicgsafe_solve(mv, b, config=SolverConfig(tol=1e-10))
        assert bool(res.converged)
        err = float(jnp.linalg.norm(res.x - xt) / jnp.linalg.norm(xt))
        assert err < 1e-7
