"""Serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --requests 8 --prompt-len 16 --max-new 24

Serves random prompts through the batched engine with random weights,
at the architecture's published widths; `--smoke` swaps in its small
same-family config (CPU).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..configs import get_config, smoke_config
from ..serving.engine import Request, ServeEngine
from .compile_cache import setup_compile_cache


def serve(cfg, *, n_requests: int, prompt_len: int, max_new: int,
          batch: int, seed: int = 0):
    """Serve `n_requests` random prompts through a fresh engine (weights
    from `seed`).  Returns (engine, completed requests, host wall s)."""
    eng = ServeEngine(cfg, batch=batch, max_len=prompt_len + max_new + 8,
                      seed=seed)
    rng = np.random.default_rng(seed)
    for rid in range(n_requests):
        eng.submit(Request(
            rid=rid,
            prompt=list(rng.integers(0, cfg.vocab, prompt_len)),
            max_new=max_new))
    t0 = time.perf_counter()
    done = eng.run()
    return eng, done, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    setup_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    _, done, dt = serve(cfg, n_requests=args.requests,
                        prompt_len=args.prompt_len, max_new=args.max_new,
                        batch=args.batch)
    tokens = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)} requests, {tokens} tokens in {dt:.1f}s "
          f"host wall, compile included ({tokens / dt:.1f} tok/s)",
          flush=True)
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out[:8]}...", flush=True)


if __name__ == "__main__":
    main()
