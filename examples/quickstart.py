"""Quickstart: the public API in ~60 lines.

  PYTHONPATH=src python examples/quickstart.py

Builds a reduced qwen3-family model, routes its matmuls through the Octopus
router, trains a handful of steps, checkpoints, restores, and greedy-decodes.
"""
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced_config
from repro.data.tokens import TokenPipeline, TokenPipelineConfig
from repro.models import LM
from repro.optim import adamw
from repro.runtime import RoutePlan, platform
from repro.train.steps import make_train_step


def main():
    platform.enable_compile_cache()
    cfg = reduced_config(get_config("qwen3-0.6b"))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # --- data + optimizer + one jit'd train step -----------------------------
    pipe = TokenPipeline(TokenPipelineConfig(vocab_size=cfg.vocab_size,
                                             seq_len=64, global_batch=8))
    opt = adamw(3e-3)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))

    for step in range(20):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state,
                                             jnp.asarray(step), batch)
        if step % 5 == 0:
            print(f"step {step:3d}  loss {float(metrics['loss']):.4f}")

    # --- checkpoint round trip ------------------------------------------------
    mgr = CheckpointManager("/tmp/quickstart_ckpt", async_writes=False)
    mgr.save({"params": params}, step=20, extra={"next_step": 20})
    restored, extra, at = mgr.restore({"params": params})
    print(f"checkpoint restored from step {at}")

    # --- greedy decode ---------------------------------------------------------
    prompt = jnp.asarray([[5, 9, 2, 7]], jnp.int32)
    cache = model.init_cache(batch=1, cache_len=32)
    # Octopus placement report for the prefill (traced abstractly, no FLOPs):
    plan = RoutePlan.trace(
        lambda p: model.prefill(p, {"tokens": prompt}, cache), restored["params"])
    print(plan.explain())
    logits, cache = jax.jit(model.prefill)(restored["params"],
                                           {"tokens": prompt}, cache)
    toks = [int(jnp.argmax(logits[0, -1, : cfg.vocab_size]))]
    for _ in range(8):
        lg, cache = jax.jit(model.decode_step)(
            restored["params"], {"tokens": jnp.asarray([[toks[-1]]])}, cache)
        toks.append(int(jnp.argmax(lg[0, 0, : cfg.vocab_size])))
    print("decoded:", toks)


if __name__ == "__main__":
    main()
