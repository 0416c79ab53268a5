"""Flash-attention forward Pallas kernel (online softmax, block-skipping).

Octopus connection: the paper's collaborative mode exists to keep the systolic
array streaming while partial-block aggregation happens elsewhere (§3.2.3).
Attention's softmax normalizer is exactly such an aggregation; the online
softmax carried in VMEM scratch (m/l/acc revolving over KV blocks) is the same
"never stall, never round-trip partials to HBM" structure, applied to the
(QK^T)V pipeline.  Causal/local block skipping implements the router's
utilization rule at the attention-block level: fully-masked MXU passes are not
issued at all.

Supported masks: "causal", "local" (sliding window, causal), "full" (bidir).
GQA is handled by the ops.py wrapper (kv head broadcast).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, mask: str, window: int, bq: int, bk: int, scale: float, n_k: int, kv_len: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk

    if mask == "causal":
        relevant = k_start <= q_start + bq - 1
    elif mask == "local":
        relevant = (k_start <= q_start + bq - 1) & (k_start + bk - 1 >= q_start - window + 1)
    else:
        relevant = k_start >= 0  # always true (traced-compatible)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)  # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < kv_len
        if mask == "causal":
            valid &= qpos >= kpos
        elif mask == "local":
            valid &= (qpos >= kpos) & (qpos - kpos < window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (bq, bk); masked -> 0
        #   (without the where, fully-masked rows hit exp(-inf - -inf) = 1)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros, not NaN
        o_ref[0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_fwd(
    q: jax.Array,  # (BH, Sq, D)
    k: jax.Array,  # (BH, Sk, D)
    v: jax.Array,  # (BH, Sk, D)
    *,
    mask: str = "causal",
    window: int = 0,
    kv_len: int | None = None,
    bq: int = 128,
    bk: int = 128,
    scale: float | None = None,
    interpret: bool,
) -> jax.Array:
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    assert sq % bq == 0 and sk % bk == 0, (q.shape, k.shape, bq, bk)
    n_k = sk // bk
    scale = scale if scale is not None else 1.0 / (d**0.5)
    kv_len = kv_len if kv_len is not None else sk
    kernel = functools.partial(
        _flash_kernel, mask=mask, window=window, bq=bq, bk=bk,
        scale=scale, n_k=n_k, kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
