"""End-to-end training driver: a ~100M-parameter LM with the full
substrate — data pipeline, AdamW + pipelined clipping, bad-step gating,
atomic checkpoints, restart recovery.

Presets:
  --preset 10m    ~10M params, 300 steps  (default; minutes on CPU)
  --preset 100m   ~114M params            (the deliverable config; pass
                  --steps to taste — ~1 min/step on this CPU)

  PYTHONPATH=src python examples/train_lm.py --preset 10m --steps 300
"""
import argparse
import time

from repro.data import DataConfig
from repro.models import ModelConfig
from repro.optim import AdamWConfig
from repro.train import TrainConfig, train

PRESETS = {
    # name: (layers, d_model, heads, kv, d_ff, vocab) ~ param count
    "1m": (2, 128, 4, 2, 512, 2048),
    "10m": (4, 384, 6, 2, 1536, 8192),       # ~14M
    "100m": (12, 768, 12, 4, 3072, 32064),   # ~114M
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="10m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="checkpoints/train_lm")
    args = ap.parse_args()

    L, d, h, kv, ff, V = PRESETS[args.preset]
    cfg = ModelConfig(name=f"lm-{args.preset}", family="dense",
                      n_layers=L, d_model=d, n_heads=h, n_kv_heads=kv,
                      d_ff=ff, vocab_size=V, remat="none")
    n_params = (V * d * 2 + L * (4 * d * d // (h // kv if kv else 1)
                                 + 3 * d * ff))
    print(f"config {cfg.name}: ~{n_params/1e6:.0f}M params, "
          f"{args.steps} steps, batch {args.batch_size}x{args.seq_len}")

    dcfg = DataConfig(batch_size=args.batch_size, seq_len=args.seq_len,
                      vocab_size=V)
    tcfg = TrainConfig(
        steps=args.steps, ckpt_every=max(50, args.steps // 4),
        ckpt_dir=args.ckpt_dir,
        opt=AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                        decay_steps=args.steps))

    t0 = time.time()
    losses = []

    def cb(step, rec):
        losses.append(rec["loss"])
        if step % 5 == 0:
            print(f"  step {step:4d} loss {rec['loss']:.4f} "
                  f"gnorm {rec['grad_norm']:.2f} "
                  f"({rec['time_s']*1e3:.0f} ms/step)", flush=True)

    out = train(cfg, dcfg, tcfg, callback=cb)
    dt = time.time() - t0
    if not losses:
        print(f"nothing to do: resumed at step {out['start_step']} "
              f">= {args.steps} (checkpoint complete)")
        return
    print(f"\ndone: steps {out['start_step']}..{args.steps}, "
          f"loss {losses[0]:.4f} -> {out['final_loss']:.4f} "
          f"in {dt:.0f}s; rejected={out['rejected_steps']}, "
          f"stragglers={out['straggler_stats']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
