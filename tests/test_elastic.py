"""Elastic scaling: a checkpoint taken under one device topology restores
bit-exactly under another (checkpoints store unsharded leaves; the
restoring job re-shards under its own in_shardings) — the contract that
lets a 512-chip job resume on 256 chips after losing a pod."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                   "src"))

_CHILD = textwrap.dedent("""
    import os, sys
    n_dev, ckpt_dir, mode = sys.argv[1], sys.argv[2], sys.argv[3]
    os.environ["XLA_FLAGS"] = \\
        f"--xla_force_host_platform_device_count={n_dev}"
    import jax, numpy as np
    from repro.configs import smoke_config
    from repro.data import DataConfig
    from repro.optim import AdamWConfig
    from repro.parallel import LogicalMesh
    from repro.train import TrainConfig, train

    cfg = smoke_config("phi3-mini-3.8b")
    dcfg = DataConfig(batch_size=4, seq_len=32, vocab_size=cfg.vocab_size)
    steps = 6 if mode == "first" else 12
    lm = None
    if int(n_dev) > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, int(n_dev) // 2), ("data", "model"))
        lm = LogicalMesh(mesh)
    tcfg = TrainConfig(steps=steps, ckpt_every=6, ckpt_dir=ckpt_dir,
                       opt=AdamWConfig(lr=1e-3, warmup_steps=2,
                                       decay_steps=12))
    out = train(cfg, dcfg, tcfg, lm=lm)
    print("START_STEP", out["start_step"])
    print("FINAL_LOSS", out["final_loss"])
""")


def _run(n_dev, ckpt_dir, mode):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _CHILD, str(n_dev),
                        str(ckpt_dir), mode],
                       capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return {l.split()[0]: float(l.split()[1])
            for l in p.stdout.splitlines()
            if l.startswith(("START_STEP", "FINAL_LOSS"))}


@pytest.mark.slow
def test_checkpoint_restores_across_topologies(tmp_path):
    # leg 1: 6 steps on an 8-device (2,4) mesh; checkpoint at step 6
    a = _run(8, tmp_path, "first")
    assert a["START_STEP"] == 0
    # leg 2: resume the same checkpoint on a SINGLE device to step 12
    b = _run(1, tmp_path, "second")
    assert b["START_STEP"] == 6
    # reference: same 12 steps uninterrupted on 1 device
    ref = _run(1, tmp_path / "ref", "second")
    assert abs(b["FINAL_LOSS"] - ref["FINAL_LOSS"]) < 0.15, \
        (b["FINAL_LOSS"], ref["FINAL_LOSS"])
