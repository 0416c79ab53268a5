"""Jit'd wrapper: GQA head handling, seq padding, block-size pick."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.util import round_up
from repro.kernels.flash_attention import flash_attention as _k


@functools.partial(
    jax.jit, static_argnames=("mask", "window", "kv_len", "interpret", "bq", "bk")
)
def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    *,
    mask: str = "causal",
    window: int = 0,
    kv_len: int | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool,
) -> jax.Array:
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    # broadcast kv heads for GQA, fold heads into batch
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hq, sk, d)
    vf = v.reshape(b * hq, sk, d)
    bq_ = min(bq, sq)
    bk_ = min(bk, sk)
    sqp, skp = round_up(sq, bq_), round_up(sk, bk_)
    kv_len_eff = kv_len if kv_len is not None else sk
    if sqp != sq:
        qf = jnp.pad(qf, ((0, 0), (0, sqp - sq), (0, 0)))
    if skp != sk:
        kf = jnp.pad(kf, ((0, 0), (0, skp - sk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, skp - sk), (0, 0)))
    out = _k.flash_fwd(
        qf, kf, vf, mask=mask, window=window, kv_len=kv_len_eff,
        bq=bq_, bk=bk_, interpret=interpret,
    )
    return out[:, :sq].reshape(b, hq, sq, d)
