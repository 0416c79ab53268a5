"""Jit'd public wrappers for the AryPE matmul kernel: padding to MXU-aligned
blocks, dtype handling, fused-vs-unfused (collaborative ablation) entry points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.util import round_up
from repro.kernels.arype_matmul import arype_matmul as _k
from repro.runtime import quant as _quant


def _pad2(x: jax.Array, m: int, n: int) -> jax.Array:
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm == 0 and pn == 0:
        return x
    return jnp.pad(x, ((0, pm), (0, pn)))


def _pick_blocks(m: int, k: int, n: int) -> tuple[int, int, int]:
    # MXU-aligned where possible; shrink for small problems so padding waste
    # stays bounded (the router should already have sent tiny shapes to VPE).
    bm = 128 if m >= 128 else max(8, round_up(m, 8))
    bn = 128 if n >= 128 else max(128, round_up(n, 128))  # lane dim stays 128
    bk = 128 if k >= 128 else max(128, round_up(k, 128))
    return bm, min(bn, 128), min(bk, 128)


@functools.partial(jax.jit, static_argnames=("activation", "interpret", "out_dtype"))
def arype_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    activation: str = "none",
    out_dtype=None,
    interpret: bool,
) -> jax.Array:
    """(M, K) @ (K, N) with fused K-block accumulation (collaborative mode)."""
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = _pick_blocks(m, k, n)
    mp, kp, np_ = round_up(m, bm), round_up(k, bk), round_up(n, bn)
    xp, wp = _pad2(x, mp, kp), _pad2(w, kp, np_)
    out = _k.mm_fused(
        xp, wp, bm=bm, bn=bn, bk=bk, activation=activation,
        out_dtype=out_dtype or x.dtype, interpret=interpret,
    )
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=(
    "scale_x", "scale_w", "activation", "interpret", "out_dtype"))
def arype_matmul_q(
    x: jax.Array,
    w: jax.Array,
    *,
    scale_x: float,
    scale_w,
    activation: str = "none",
    out_dtype=None,
    interpret: bool,
) -> jax.Array:
    """Quantized (M, K) @ (K, N): f32 operands clip-rounded to symmetric int8
    on the given per-layer scales (``scale_w`` a float or a per-output-channel
    tuple), contracted with fused int32 accumulation, dequantized to
    ``out_dtype`` before the activation.  Scales are static — they come from
    a calibration artifact and are fixed per layer."""
    m, k = x.shape
    _, n = w.shape
    xq = _quant.quantize_i8(x, scale_x)
    wq = _quant.quantize_i8(w, scale_w)
    dq = jnp.asarray(_quant.dequant_row(scale_x, scale_w, n))[None, :]
    bm, bn, bk = _pick_blocks(m, k, n)
    mp, kp, np_ = round_up(m, bm), round_up(k, bk), round_up(n, bn)
    xq, wq = _pad2(xq, mp, kp), _pad2(wq, kp, np_)
    dq = _pad2(dq, 1, np_)
    out = _k.mm_fused_q(
        xq, wq, dq, bm=bm, bn=bn, bk=bk,
        activation=activation, out_dtype=out_dtype or x.dtype, interpret=interpret,
    )
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("activation", "interpret", "out_dtype"))
def arype_matmul_unfused(
    x: jax.Array,
    w: jax.Array,
    *,
    activation: str = "none",
    out_dtype=None,
    interpret: bool,
) -> jax.Array:
    """'wo/ collaborating' ablation: partial K-blocks written to HBM, then a
    separate aggregation pass (paper Table 6 baseline)."""
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = _pick_blocks(m, k, n)
    mp, kp, np_ = round_up(m, bm), round_up(k, bk), round_up(n, bn)
    xp, wp = _pad2(x, mp, kp), _pad2(w, kp, np_)
    partials = _k.mm_unfused_partials(xp, wp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    out = partials.sum(axis=0)  # separate aggregation pass (the VU's job, serialized)
    if activation == "relu":
        out = jnp.maximum(out, 0.0)
    elif activation == "silu":
        out = out * jax.nn.sigmoid(out)
    elif activation == "gelu":
        out = jax.nn.gelu(out)
    return out[:m, :n].astype(out_dtype or x.dtype)
