"""The paper's solver as the training optimizer: truncated Gauss-Newton
steps with p-BiCGSafe as the inner Krylov solver (DESIGN.md §4).

  PYTHONPATH=src python examples/newton_krylov_lm.py
"""
import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models import forward, init_params, loss_fn
from repro.optim.newton_krylov import NewtonKrylovConfig, newton_krylov_step


def main():
    cfg = smoke_config("phi3-mini-3.8b").replace(
        n_layers=2, dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                          0, cfg.vocab_size)}

    def logits_fn(p, b):
        return forward(p, cfg, b)[0]

    def lossf(p, b):
        return loss_fn(p, cfg, b)[0]

    nk = NewtonKrylovConfig(damping=1e-2, inner_maxiter=12, inner_tol=1e-2,
                            trust_radius=5.0)
    print(f"Newton-Krylov (inner solver: p-BiCGSafe) on {cfg.name} smoke")
    loss = float(lossf(params, batch))
    print(f"  step 0: loss {loss:.4f}")
    for step in range(1, 6):
        params, m = newton_krylov_step(lossf, logits_fn, params, batch, nk)
        print(f"  step {step}: loss {float(m['new_loss']):.4f} "
              f"(inner iters {int(m['inner_iters'])}, "
              f"relres {float(m['inner_relres']):.1e}, "
              f"step scale {float(m['step_scale'])})")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
