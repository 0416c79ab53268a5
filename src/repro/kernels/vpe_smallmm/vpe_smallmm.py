"""VPE-path Pallas kernel: small/skinny matmul as broadcast-multiply +
tree-reduce on the VPU, with a fused activation stage.

This is the TPU analogue of the paper's VPE SIMDU (§3.2.1): each sub-lane is a
4-wide multiplier bank feeding an adder tree plus an activation unit, used for
matmuls whose dims are too small to fill the systolic array (the
"under-utilization" regime, e.g. the first CNN layer's (w,3)x(3,32)).

On TPU a matmul with K or N « 128 wastes most of a 128x128 MXU pass; the same
contraction expressed as an elementwise product + lane reduction runs on the
8x128 VPU at full lane utilization.  The kernel keeps the whole (M-block, K, N)
working set in VMEM, multiplies with x broadcast along N, and reduces over K
with ``jnp.sum`` (lowered to the VPU adder tree).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _vpe_kernel(x_ref, w_ref, o_ref, *, activation: str):
    # x_ref: (bm, K), w_ref: (K, N) — K, N small (router guarantees).
    x = x_ref[...].astype(jnp.float32)  # (bm, K)
    w = w_ref[...].astype(jnp.float32)  # (K, N)
    # broadcast-multiply (VPU) then adder-tree reduce over K
    prod = x[:, :, None] * w[None, :, :]  # (bm, K, N)
    out = jnp.sum(prod, axis=1)  # (bm, N)
    if activation == "relu":
        out = jnp.maximum(out, 0.0)
    elif activation == "silu":
        out = out * jax.nn.sigmoid(out)
    elif activation == "gelu":
        out = jax.nn.gelu(out)
    o_ref[...] = out.astype(o_ref.dtype)


def _vpe_q_kernel(x_ref, w_ref, dq_ref, o_ref, *, activation: str):
    """Int8 variant: integer broadcast-multiply + int32 adder-tree reduce,
    dequant + activation fused at the end — the paper's fixed-point SIMDU
    sub-lane (int multiplier bank, int adder tree, activation unit).
    ``dq_ref`` is the (1, N) per-output-channel dequant row."""
    x = x_ref[...].astype(jnp.int32)  # (bm, K) int8 widened for the MAC
    w = w_ref[...].astype(jnp.int32)  # (K, N)
    prod = x[:, :, None] * w[None, :, :]  # (bm, K, N) exact int32 products
    acc = jnp.sum(prod, axis=1)  # (bm, N) int32
    out = acc.astype(jnp.float32) * dq_ref[0, :]
    if activation == "relu":
        out = jnp.maximum(out, 0.0)
    elif activation == "silu":
        out = out * jax.nn.sigmoid(out)
    elif activation == "gelu":
        out = jax.nn.gelu(out)
    o_ref[...] = out.astype(o_ref.dtype)


def vpe_mm(
    x: jax.Array,
    w: jax.Array,
    *,
    bm: int = 256,
    activation: str = "none",
    out_dtype=None,
    interpret: bool,
) -> jax.Array:
    """x: (M, K) @ w: (K, N), M a multiple of bm (ops.py pads), K*N small."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0, (x.shape, w.shape, bm)
    kernel = functools.partial(_vpe_kernel, activation=activation)
    return pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or x.dtype),
        interpret=interpret,
    )(x, w)


def vpe_mm_q(
    x_q: jax.Array,
    w_q: jax.Array,
    dequant: jax.Array,
    *,
    bm: int = 256,
    activation: str = "none",
    out_dtype=jnp.float32,
    interpret: bool,
) -> jax.Array:
    """Int8 x_q: (M, K) @ w_q: (K, N) with int32 accumulation; M a multiple
    of bm (ops.py pads — zero int8 pads are exact).  ``dequant`` is the
    (1, N) per-output-channel ``scale_x * scale_w`` row."""
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2 and m % bm == 0, (x_q.shape, w_q.shape, bm)
    assert dequant.shape == (1, n), (dequant.shape, n)
    kernel = functools.partial(_vpe_q_kernel, activation=activation)
    return pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(x_q, w_q, dequant)
