"""Weights of a configuration's two models, made on the device from the seed
in one jitted call, in float32 (the type they are served in), under the
parameter names the pipeline's engines read."""
from __future__ import annotations

import numpy as np


def shapes(cfg: dict) -> dict:
    """{"packet": {name: shape}, "flow": {name: shape}} for a configuration."""
    dims = cfg["packet_model"]["dims"]
    packet = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        packet[f"w{i}"], packet[f"b{i}"] = (a, b), (b,)
    fm = cfg["flow_model"]
    flow = {}
    if fm["kind"] == "cnn":
        ch, k = fm["channels"], fm["kernel"]
        for i, (ci, co) in enumerate(zip(ch[:-1], ch[1:])):
            flow[f"conv{i}"], flow[f"convb{i}"] = (k * ci, co), (co,)
        L = fm["seq"]
        for _ in range(len(ch) - 1):
            L = -(-L // 2)
        flow["fc_w"], flow["fc_b"] = (L * ch[-1], fm["fc"]), (fm["fc"],)
        flow["out_w"], flow["out_b"] = (fm["fc"], fm["classes"]), (fm["classes"],)
    elif fm["kind"] == "transformer":
        b, d, m = fm["bytes"], fm["d_k"], fm["mlp"]
        flow = {"wq": (b, d), "wk": (b, d), "wv": (b, d),
                "mlp1": (d, m), "mlp1_b": (m,), "mlp2": (m, d), "mlp2_b": (d,),
                "cls_w": (d, fm["classes"]), "cls_b": (fm["classes"],)}
    else:
        raise ValueError(f"unknown flow model {fm['kind']!r}")
    return {"packet": packet, "flow": flow}


def weight_key(seed: int) -> int:
    """A 32-bit key for the weights, derived from ``seed`` (any size)."""
    return int(np.random.SeedSequence([int(seed), 2]).generate_state(1)[0])


def make_weights(cfg: dict, seed: int, device=None) -> dict:
    """{"packet": params, "flow": params} on ``device``: fan-in scaled
    normal weights and 0.1-scaled normal biases, one jitted call."""
    import jax
    import jax.numpy as jnp

    spec = shapes(cfg)
    names = [(g, n) for g in ("packet", "flow") for n in sorted(spec[g])]

    def init(key):
        keys = jax.random.split(key, len(names))
        out = {"packet": {}, "flow": {}}
        for k, (g, n) in zip(keys, names):
            shape = spec[g][n]
            scale = 0.1 if len(shape) == 1 else 1.0 / np.sqrt(shape[0])
            out[g][n] = jax.random.normal(k, shape, jnp.float32) * scale
        return out

    key = jax.random.key(weight_key(seed))
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(init)(key)
