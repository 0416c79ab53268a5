"""Jit'd wrapper for the VPE small-matmul kernel: M-padding + block pick."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.util import round_up
from repro.kernels.vpe_smallmm import vpe_smallmm as _k
from repro.runtime import quant as _quant

# VMEM working-set budget for the (bm, K, N) product tile, in fp32 elements.
_VMEM_ELEMS = 1 << 20  # 4 MB


@functools.partial(jax.jit, static_argnames=("activation", "interpret", "out_dtype"))
def vpe_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    activation: str = "none",
    out_dtype=None,
    interpret: bool,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    bm = max(8, min(256, _VMEM_ELEMS // max(k * n, 1)))
    bm = max(8, (bm // 8) * 8)
    mp = round_up(m, bm)
    xp = jnp.pad(x, ((0, mp - m), (0, 0))) if mp != m else x
    out = _k.vpe_mm(
        xp, w, bm=bm, activation=activation, out_dtype=out_dtype or x.dtype, interpret=interpret
    )
    return out[:m]


@functools.partial(jax.jit, static_argnames=(
    "scale_x", "scale_w", "activation", "interpret", "out_dtype"))
def vpe_matmul_q(
    x: jax.Array,
    w: jax.Array,
    *,
    scale_x: float,
    scale_w,
    activation: str = "none",
    out_dtype=None,
    interpret: bool,
) -> jax.Array:
    """Quantized VPE small-matmul: f32 operands clip-rounded to symmetric
    int8 on the per-layer scales (``scale_w`` a float or a per-output-channel
    tuple), int32 accumulation in the kernel, f32 dequant before the
    activation."""
    m, k = x.shape
    _, n = w.shape
    xq = _quant.quantize_i8(x, scale_x)
    wq = _quant.quantize_i8(w, scale_w)
    dq = jnp.asarray(_quant.dequant_row(scale_x, scale_w, n))[None, :]
    bm = max(8, min(256, _VMEM_ELEMS // max(k * n, 1)))
    bm = max(8, (bm // 8) * 8)
    mp = round_up(m, bm)
    xq = jnp.pad(xq, ((0, mp - m), (0, 0))) if mp != m else xq
    out = _k.vpe_mm_q(
        xq, wq, dq, bm=bm,
        activation=activation, out_dtype=out_dtype or x.dtype, interpret=interpret,
    )
    return out[:m]
