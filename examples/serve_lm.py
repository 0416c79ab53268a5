"""Continuous-batching LM serving demo (slot-based engine, per-slot lengths).

  PYTHONPATH=src python examples/serve_lm.py --arch gemma3-1b --requests 6
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.models import LM
from repro.runtime import platform
from repro.serving import Request, ServeConfig, ServeEngine


def main():
    platform.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = reduced_config(get_config(args.arch))
    if not cfg.supports_decode:
        raise SystemExit(f"{args.arch} is encoder-only")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(batch_slots=args.slots, cache_len=128))

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 6 + i % 5),
                           max_new=args.max_new))
    t0 = time.perf_counter()
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"[serve_lm] {len(done)} requests, {toks} tokens, {toks/dt:.1f} tok/s")
    for r in done:
        print(f"  rid={r.rid} out={r.out_tokens}")


if __name__ == "__main__":
    main()
