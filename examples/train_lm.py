"""End-to-end training driver: train an LM on the synthetic Markov stream for
a few hundred steps with checkpointing and (optional) crash/restart.

  PYTHONPATH=src python examples/train_lm.py                 # ~6M params, 200 steps
  PYTHONPATH=src python examples/train_lm.py --size 100m     # ~100M params
  PYTHONPATH=src python examples/train_lm.py --crash-at 100  # then re-run to resume

The loss must decrease measurably (the stream has ~2 bits of conditional
entropy vs 8 bits marginal).
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.configs import get_config
from repro.data.tokens import TokenPipelineConfig
from repro.runtime import platform
from repro.train.loop import Trainer, TrainLoopConfig


def size_cfg(size: str):
    base = get_config("qwen3-0.6b")
    if size == "small":  # ~6M params
        return base.replace(d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                            d_ff=512, vocab_size=256, num_superblocks=4,
                            vocab_round_to=16, fsdp=False,
                            param_dtype="float32", compute_dtype="float32")
    if size == "20m":
        return base.replace(d_model=256, num_heads=8, num_kv_heads=4, head_dim=32,
                            d_ff=1024, vocab_size=512, num_superblocks=8,
                            vocab_round_to=16, fsdp=False)
    if size == "100m":
        return base.replace(d_model=512, num_heads=8, num_kv_heads=4, head_dim=64,
                            d_ff=2048, vocab_size=4096, num_superblocks=16,
                            vocab_round_to=64, fsdp=False)
    raise ValueError(size)


def main():
    platform.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="small", choices=["small", "20m", "100m"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/train_lm_ckpt")
    ap.add_argument("--crash-at", type=int, default=None)
    args = ap.parse_args()

    cfg = size_cfg(args.size)
    loop = TrainLoopConfig(
        total_steps=args.steps, checkpoint_every=max(args.steps // 4, 10),
        checkpoint_dir=args.ckpt, lr=args.lr, warmup_steps=20, log_every=20,
        fail_at_step=args.crash_at,
    )
    data = TokenPipelineConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                               global_batch=args.batch, branching=4)
    trainer = Trainer(cfg, loop, data)
    out = trainer.run()
    h = out["history"]
    print(f"[train_lm] loss {h[0]:.3f} -> {h[-1]:.3f} over {len(h)} steps "
          f"(median {out['median_step_time_s']*1e3:.0f} ms/step)")
    assert h[-1] < h[0] - 0.5, "loss did not decrease enough"


if __name__ == "__main__":
    main()
