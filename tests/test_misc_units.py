"""Unit tests for smaller pieces: preconditioning, LR schedule, pipelined
clipping, data prefetcher, solver mesh helper."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (JacobiPreconditioner, SolverConfig, as_matvec,
                        pbicgsafe_solve, preconditioned_matvec)
from repro.core import matrices as M
from repro.optim import AdamWConfig
from repro.optim.adamw import schedule
from repro.optim.clipping import (global_norm, pipelined_clip,
                                  pipelined_clip_init)


def test_jacobi_preconditioner_reduces_iterations(x64):
    op, b, xt = M.anisotropic3d(12, eps=1e-3)
    plain = pbicgsafe_solve(op.matvec, b, config=SolverConfig(maxiter=4000))
    pre = JacobiPreconditioner.from_operator(op)
    mv = preconditioned_matvec(op, pre)
    cond = pbicgsafe_solve(mv, pre.apply(b),
                           config=SolverConfig(maxiter=4000))
    assert bool(cond.converged)
    # preconditioned system solves the same problem
    err = float(jnp.linalg.norm(cond.x - xt) / jnp.linalg.norm(xt))
    assert err < 1e-5
    if bool(plain.converged):
        assert int(cond.iterations) <= int(plain.iterations) + 5


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1e-3, warmup_steps=10, decay_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(schedule(cfg, jnp.asarray(s))) for s in
           [0, 5, 10, 50, 100, 1000]]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(5e-4)
    assert lrs[2] == pytest.approx(1e-3, rel=0.2)
    assert lrs[-1] == pytest.approx(1e-4, rel=0.01)   # min lr floor
    assert lrs[3] > lrs[4]


def test_pipelined_clip_uses_stale_norm():
    g1 = {"w": jnp.full((4,), 100.0)}     # norm 200
    g2 = {"w": jnp.full((4,), 0.001)}
    st = pipelined_clip_init()
    s1, st = pipelined_clip(g1, st, max_norm=1.0)
    # first step: no previous norm -> uses fresh (200) -> scale 1/200
    assert float(s1) == pytest.approx(1.0 / float(global_norm(g1)))
    s2, st = pipelined_clip(g2, st, max_norm=1.0)
    # second step clips with step-1's norm (stale): tiny scale despite
    # tiny fresh gradient — the one-step-stale contract
    assert float(s2) == pytest.approx(1.0 / float(global_norm(g1)))
    s3, _ = pipelined_clip(g2, st, max_norm=1.0)
    assert float(s3) == 1.0               # now sees g2's small norm


def test_prefetcher_yields_in_order():
    from repro.data import DataConfig, make_dataset
    from repro.data.pipeline import prefetch
    cfg = DataConfig(batch_size=2, seq_len=16, vocab_size=64)
    fn = make_dataset(cfg)
    it = prefetch(fn, start_step=0)
    got = [next(it) for _ in range(3)]
    for step, b in enumerate(got):
        np.testing.assert_array_equal(b["tokens"], fn(step)["tokens"])


def test_ring_shift_is_exact_shift():
    """ring_shift on a 1-axis mesh == roll with zero boundary."""
    import subprocess, sys, os, textwrap
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), os.pardir, "src"))
    env.pop("XLA_FLAGS", None)
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.core.distributed import ring_shift
        mesh = make_mesh((4, 2), ("a", "b"))
        x = jnp.arange(8.0).reshape(8, 1)
        def f(x):
            fwd = ring_shift(x, ("a", "b"), (4, 2), True)
            bwd = ring_shift(x, ("a", "b"), (4, 2), False)
            return fwd, bwd
        fwd, bwd = jax.jit(jax.shard_map(f, mesh=mesh,
            in_specs=P(("a", "b")), out_specs=(P(("a", "b")),) * 2))(x)
        np.testing.assert_allclose(np.asarray(fwd).ravel(),
                                   [0,0,1,2,3,4,5,6])
        np.testing.assert_allclose(np.asarray(bwd).ravel(),
                                   [1,2,3,4,5,6,7,0])
        print("RING OK")
    """)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "RING OK" in p.stdout


def test_compile_cache_dir_from_env_or_fixed_checkout_path(monkeypatch):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR when
    set (nothing else is configured), otherwise one fixed path inside
    the checkout."""
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_peak_table_refuses_unknown_device_kind():
    from repro.launch.peaks import chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert v5e.hbm_bytes_per_s == 819e9 and v5e.bf16_flops == 197e12
    with pytest.raises(KeyError, match="no published peak rates"):
        chip_peaks("cpu")
