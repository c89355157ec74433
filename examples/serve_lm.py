"""Serving example: batched requests through the continuous-batching
engine (prefill + slot decode), greedy decoding.

  PYTHONPATH=src python examples/serve_lm.py --arch qwen3-8b
"""
import argparse
import time

import numpy as np

from repro.configs import smoke_config
from repro.serve import Request, ServeConfig, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    eng = ServingEngine(cfg, ServeConfig(max_batch=4, max_len=128))
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(rng.choice([8, 8, 16]))
        eng.submit(Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                                    plen).astype(int)),
                           max_new_tokens=args.max_new))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    toks = sum(len(r.output) for r in done)
    for r in done:
        print(f"  req {r.rid}: prompt[{len(r.prompt)}] -> "
              f"{r.output[:6]}... ({len(r.output)} tokens)")
    print(f"{len(done)} requests / {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s incl. compile)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
