"""AryPE-path Pallas kernel: MXU-aligned blocked matmul with *fused* K-block
accumulation in VMEM scratch.

This is the TPU-native analogue of the paper's heterogeneous collaborative
computing (§3.2.3): on the FPGA, AryPE streams (l,k)x(k,k) tiles while the
VPE's vector unit aggregates partial blocks through an on-chip ping-pong
buffer, so the systolic array never stalls.  On TPU the same property is
obtained by carrying the partial block in a VMEM accumulator across the K grid
dimension (``acc_ref``): partial blocks never round-trip to HBM, and Pallas's
grid pipelining overlaps the next tile's HBM->VMEM copy with the current MXU
pass (the ping-pong buffer).

The *unfused* variant (`arype_matmul_unfused` in ops.py) reproduces the
paper's "wo/ collaborating" ablation: every K-block partial is written back to
HBM and aggregated in a separate pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_fused_kernel(x_ref, w_ref, o_ref, acc_ref, *, activation: str, n_k: int):
    """grid = (M/bm, N/bn, K/bk); K innermost so acc_ref revolves in VMEM."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        out = acc_ref[...]
        if activation == "relu":
            out = jnp.maximum(out, 0.0)
        elif activation == "silu":
            out = out * jax.nn.sigmoid(out)
        elif activation == "gelu":
            out = jax.nn.gelu(out)
        o_ref[...] = out.astype(o_ref.dtype)


def _mm_fused_q_kernel(x_ref, w_ref, dq_ref, o_ref, acc_ref, *, activation: str,
                       n_k: int):
    """Int8 variant of the fused kernel: int8 operand tiles, int32 VMEM
    accumulator across the K grid, dequant + activation in the epilogue.
    Mirrors the paper's fixed-point AryPE datapath (int MACs, one scale
    multiply on the way out).  ``dq_ref`` is the (1, bn) dequant row —
    ``scale_x * scale_w`` per output channel."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.int32
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * dq_ref[0, :]
        if activation == "relu":
            out = jnp.maximum(out, 0.0)
        elif activation == "silu":
            out = out * jax.nn.sigmoid(out)
        elif activation == "gelu":
            out = jax.nn.gelu(out)
        o_ref[...] = out.astype(o_ref.dtype)


def _mm_partial_kernel(x_ref, w_ref, o_ref):
    """Unfused ablation: each (i, j, l) grid cell writes its own partial block
    to HBM (out has a leading K-blocks dim); aggregation is a separate pass."""
    o_ref[0, :, :] = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)


def mm_fused(
    x: jax.Array,
    w: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    activation: str = "none",
    out_dtype=None,
    interpret: bool,
) -> jax.Array:
    """x: (M, K) @ w: (K, N) -> (M, N).  Dims must be multiples of the blocks
    (ops.py pads).  ``interpret=True`` on CPU; on a real TPU pass False."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, w.shape, bm, bn, bk)
    n_k = k // bk
    kernel = functools.partial(_mm_fused_kernel, activation=activation, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)


def mm_fused_q(
    x_q: jax.Array,
    w_q: jax.Array,
    dequant: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    activation: str = "none",
    out_dtype=jnp.float32,
    interpret: bool,
) -> jax.Array:
    """Int8 x_q: (M, K) @ w_q: (K, N) -> f32-ish (M, N), int32 accumulation.

    ``dequant`` is the (1, N) per-output-channel ``scale_x * scale_w`` row;
    integer accumulation is exact, so block tiling/padding cannot perturb the
    result (zero int8 pads contribute zero int32 products)."""
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    assert dequant.shape == (1, n), (dequant.shape, n)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x_q.shape, w_q.shape, bm, bn, bk)
    n_k = k // bk
    kernel = functools.partial(_mm_fused_q_kernel, activation=activation, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
            pl.BlockSpec((1, bn), lambda i, j, l: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, dequant)


def mm_unfused_partials(
    x: jax.Array,
    w: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool,
) -> jax.Array:
    """Returns partial blocks (K/bk, M, N) in fp32 — the 'wo/ collaborating'
    ablation where block aggregation is a separate HBM pass."""
    m, k = x.shape
    _, n = w.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    return pl.pallas_call(
        _mm_partial_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, j, l: (l, i, j)),
        out_shape=jax.ShapeDtypeStruct((k // bk, m, n), jnp.float32),
        interpret=interpret,
    )(x, w)
