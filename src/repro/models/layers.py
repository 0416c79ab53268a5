"""Transformer-family layers: norms, RoPE, attention (blockwise train path,
cached decode path, sliding-window ring caches, cross-attention), SwiGLU MLP,
and capacity-based MoE with expert parallelism.

Every matmul dispatches through the Octopus router (repro.core.router), making
the paper's heterogeneous placement a global property of the framework.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.common.util import ceil_div
from repro.configs.base import ArchConfig
from repro.core import router
from repro.distributed.act import shard_act
from repro.models.spec import ParamSpec
from repro.runtime import RuntimeConfig, resolve_config

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms & RoPE
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) rotary over D; positions: (..., S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention: parameter specs
# ---------------------------------------------------------------------------

def attn_specs(cfg: ArchConfig, *, cross: bool = False) -> dict:
    dt = cfg.param_dtype
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    specs = {
        "ln": ParamSpec((d,), ("embed",), "zeros", dtype=dt),
        "wq": ParamSpec((d, qd), ("embed", "heads"), "normal", dtype=dt),
        "wk": ParamSpec((d, kvd), ("embed", "kv_heads"), "normal", dtype=dt),
        "wv": ParamSpec((d, kvd), ("embed", "kv_heads"), "normal", dtype=dt),
        "wo": ParamSpec((qd, d), ("heads", "embed"), "normal", dtype=dt),
    }
    if cfg.use_qk_norm:
        specs["q_norm"] = ParamSpec((cfg.head_dim,), (None,), "zeros", dtype=dt)
        specs["k_norm"] = ParamSpec((cfg.head_dim,), (None,), "zeros", dtype=dt)
    if cross:
        specs["ln_kv"] = ParamSpec((d,), ("embed",), "zeros", dtype=dt)
    return specs


# ---------------------------------------------------------------------------
# Attention: training / prefill path (blockwise, online softmax)
# ---------------------------------------------------------------------------

def _naive_attention(q, k, v, *, kind: str, window: int, q_offset=0, kv_len=None):
    """q: (B,S,Hkv,G,D); k/v: (B,Sk,Hkv,D).  Materializes scores; small S only."""
    b, s, hkv, g, dh = q.shape
    sk = k.shape[1]
    scale = 1.0 / np.sqrt(dh)
    s_ = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    qpos = q_offset + jnp.arange(s)[:, None]
    kpos = jnp.arange(sk)[None, :]
    valid = jnp.ones((s, sk), bool)
    if kv_len is not None:
        valid &= kpos < kv_len
    if kind == "causal":
        valid &= qpos >= kpos
    elif kind == "local":
        valid &= (qpos >= kpos) & (qpos - kpos < window)
    s_ = jnp.where(valid[None, None, None], s_, NEG_INF)
    m = s_.max(axis=-1, keepdims=True)
    p = jnp.where(valid[None, None, None], jnp.exp(s_ - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    l = jnp.where(l == 0, 1.0, l)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p / l, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _blockwise_attention(q, k, v, *, kind: str, window: int, chunk_q: int,
                         chunk_kv: int, unroll: bool = False,
                         av_dtype=jnp.float32):
    """Flash-style blockwise attention in pure jnp: all q chunks vectorized,
    lax.scan over kv chunks carrying (m, l, acc).  Memory O(S * chunk_kv)."""
    b, s, hkv, g, dh = q.shape
    sk = k.shape[1]
    cq = min(chunk_q, s)
    ck = min(chunk_kv, sk)
    nq, nk = ceil_div(s, cq), ceil_div(sk, ck)
    sp, skp = nq * cq, nk * ck
    if sp != s:
        q = jnp.pad(q, ((0, 0), (0, sp - s), (0, 0), (0, 0), (0, 0)))
    if skp != sk:
        k = jnp.pad(k, ((0, 0), (0, skp - sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skp - sk), (0, 0), (0, 0)))
    scale = 1.0 / np.sqrt(dh)
    qc = q.reshape(b, nq, cq, hkv, g, dh).astype(jnp.float32) * scale
    kc = k.reshape(b, nk, ck, hkv, dh)
    vc = v.reshape(b, nk, ck, hkv, dh)
    qpos = (jnp.arange(nq)[:, None] * cq + jnp.arange(cq)[None, :])  # (nq, cq)

    def step(carry, kv_j):
        m_prev, l_prev, acc = carry
        kj, vj, j = kv_j
        s_ = jnp.einsum("bnqhgd,bkhd->bnhgqk", qc, kj.astype(jnp.float32))
        kpos = j * ck + jnp.arange(ck)  # (ck,)
        valid = (kpos[None, None] < sk) & jnp.ones((nq, cq, ck), bool)
        if kind == "causal":
            valid &= qpos[:, :, None] >= kpos[None, None, :]
        elif kind == "local":
            dpos = qpos[:, :, None] - kpos[None, None, :]
            valid &= (dpos >= 0) & (dpos < window)
        s_ = jnp.where(valid[None, :, None, None], s_, NEG_INF)
        m_new = jnp.maximum(m_prev, s_.max(axis=-1))
        p = jnp.where(valid[None, :, None, None], jnp.exp(s_ - m_new[..., None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bnhgqk,bkhd->bnhgqd", p.astype(av_dtype), vj.astype(av_dtype),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = shard_act(jnp.full((b, nq, hkv, g, cq), NEG_INF, jnp.float32),
                   "batch", None, "heads", None, None)
    l0 = shard_act(jnp.zeros((b, nq, hkv, g, cq), jnp.float32),
                   "batch", None, "heads", None, None)
    a0 = shard_act(jnp.zeros((b, nq, hkv, g, cq, dh), jnp.float32),
                   "batch", None, "heads", None, None, None)
    (m, l, acc), _ = lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nk)),
        unroll=True if unroll else 1,
    )
    l = jnp.where(l == 0, 1.0, l)
    out = (acc / l[..., None]).astype(q.dtype)  # (b, nq, hkv, g, cq, dh)
    out = jnp.moveaxis(out, (1, 4), (1, 2)).reshape(b, sp, hkv, g, dh)
    return out[:, :s]


def attention_core(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,
    *,
    kind: str,  # causal|local|full
    cfg: Optional[ArchConfig] = None,  # pulls window/use_pallas/impl/unroll/av_dtype
    window: int = 0,
    chunk_q: int = 512,
    chunk_kv: int = 512,
    use_pallas: bool = False,
    impl: str = "auto",  # auto|naive|blockwise
    unroll: bool = False,
    av_dtype="float32",
) -> jax.Array:
    if cfg is not None:
        window, use_pallas, impl = cfg.window_size, cfg.use_pallas, cfg.attn_impl
        unroll, av_dtype = cfg.inner_unroll, cfg.attn_av_dtype
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    if use_pallas:
        from repro.kernels.flash_attention import flash_attention

        mask = {"causal": "causal", "local": "local", "full": "full"}[kind]
        out = flash_attention(
            jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
            mask=mask, window=window, interpret=resolve_config(None).interpret,
        )
        return jnp.moveaxis(out, 1, 2)
    # For TP cleanliness, expand KV heads to the full head count (the repeated
    # copies shard over the model axis together with q heads).
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    k = shard_act(k, "batch", None, "heads", None)
    v = shard_act(v, "batch", None, "heads", None)
    qg = q.reshape(b, s, hq, 1, dh)
    if impl == "naive" or (impl == "auto" and s * k.shape[1] <= (1 << 20)):
        out = _naive_attention(qg, k, v, kind=kind, window=window)
    else:
        out = _blockwise_attention(qg, k, v, kind=kind, window=window,
                                   chunk_q=chunk_q, chunk_kv=chunk_kv,
                                   unroll=unroll, av_dtype=jnp.dtype(av_dtype))
    return out.reshape(b, s, hq, dh)


# ---------------------------------------------------------------------------
# Attention: cached decode path
# ---------------------------------------------------------------------------

class AttnCache(NamedTuple):
    k: jax.Array  # (B, C, Hkv, D) -- C = full length (global) or window (local ring)
    v: jax.Array
    pos: jax.Array  # (B, C) int32 absolute position stored in each slot (-1 = empty)


def init_attn_cache(cfg: ArchConfig, batch: int, cache_len: int, *, kind: str,
                    dtype=jnp.bfloat16) -> AttnCache:
    c = min(cache_len, cfg.window_size) if kind == "local" and cfg.window_size else cache_len
    return AttnCache(
        k=jnp.zeros((batch, c, cfg.num_kv_heads, cfg.head_dim), dtype),
        v=jnp.zeros((batch, c, cfg.num_kv_heads, cfg.head_dim), dtype),
        pos=jnp.full((batch, c), -1, jnp.int32),
    )


def cache_write(cache: AttnCache, k_new: jax.Array, v_new: jax.Array,
                lengths: jax.Array, *, kind: str, window: int) -> AttnCache:
    """Write S_new tokens at per-sample positions lengths..lengths+S_new-1.
    Local caches are ring buffers indexed by absolute position % window."""
    b, s_new = k_new.shape[0], k_new.shape[1]
    cap = cache.k.shape[1]
    abs_pos = lengths[:, None] + jnp.arange(s_new)[None, :]  # (B, S_new)
    idx = abs_pos % cap if kind == "local" else jnp.minimum(abs_pos, cap - 1)
    bidx = jnp.arange(b)[:, None].repeat(s_new, axis=1)
    return AttnCache(
        k=cache.k.at[bidx, idx].set(k_new.astype(cache.k.dtype)),
        v=cache.v.at[bidx, idx].set(v_new.astype(cache.v.dtype)),
        pos=cache.pos.at[bidx, idx].set(abs_pos),
    )


def attention_decode(
    q: jax.Array,  # (B, S_new, Hq, D)  (S_new typically 1)
    cache: AttnCache,
    lengths: jax.Array,  # (B,) length BEFORE this step's tokens
    *,
    kind: str,
    window: int = 0,
) -> jax.Array:
    b, sn, hq, dh = q.shape
    hkv = cache.k.shape[2]
    g = hq // hkv
    scale = 1.0 / np.sqrt(dh)
    qg = q.reshape(b, sn, hkv, g, dh).astype(jnp.float32) * scale
    s_ = jnp.einsum("bqhgd,bkhd->bhgqk", qg, cache.k.astype(jnp.float32))
    qpos = lengths[:, None] + jnp.arange(sn)[None, :]  # (B, S_new) absolute
    kpos = cache.pos  # (B, C) absolute (-1 empty)
    valid = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= qpos[:, :, None])
    if kind == "local":
        valid &= (qpos[:, :, None] - kpos[:, None, :]) < window
    s_ = jnp.where(valid[:, None, None, :, :], s_, NEG_INF)
    m = s_.max(axis=-1, keepdims=True)
    p = jnp.where(valid[:, None, None, :, :], jnp.exp(s_ - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    l = jnp.where(l == 0, 1.0, l)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p / l, cache.v.astype(jnp.float32))
    return out.astype(q.dtype).reshape(b, sn, hq, dh)


# ---------------------------------------------------------------------------
# Attention: full layer apply
# ---------------------------------------------------------------------------

def _theta_for(cfg: ArchConfig, kind: str) -> float:
    return cfg.rope_theta_local if kind == "local" else cfg.rope_theta


def attn_apply(
    p: dict,
    x: jax.Array,  # (B, S, D)
    cfg: ArchConfig,
    *,
    kind: str,  # causal|local|full|cross
    positions: Optional[jax.Array] = None,  # (B, S)
    cross_kv: Optional[jax.Array] = None,  # (B, T, D) modality embeddings
    cache: Optional[AttnCache] = None,
    lengths: Optional[jax.Array] = None,
    mode: str = "train",  # train | prefill | decode
) -> tuple[jax.Array, Optional[AttnCache]]:
    b, s, d = x.shape
    # Projections stay on the dot path even under cfg.use_pallas: the Pallas
    # budget of this layer goes to the flash-attention kernel, not the QKV/O
    # matmuls (same split as the pre-runtime code).
    mm = functools.partial(router.matmul, out_dtype=x.dtype,
                           config=RuntimeConfig.from_arch(cfg, use_pallas=False))
    h = rms_norm(x, p["ln"])
    q = mm(h, p["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
    q = shard_act(q, "batch", None, "heads", None)

    if kind == "cross":
        if mode == "decode":
            assert cache is not None  # image kv precomputed at prefill
            k, v, new_cache = cache.k, cache.v, cache
        else:
            kvsrc = rms_norm(cross_kv, p["ln_kv"])
            t = kvsrc.shape[1]
            k = mm(kvsrc, p["wk"]).reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
            v = mm(kvsrc, p["wv"]).reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
            new_cache = AttnCache(k=k, v=v, pos=jnp.tile(jnp.arange(t)[None], (b, 1)))
        if cfg.use_qk_norm:
            q = rms_norm(q, p["q_norm"])
            k = rms_norm(k, p["k_norm"]) if mode != "decode" else k
        out = attention_core(q, k, v, kind="full", cfg=cfg)
        out = mm(out.reshape(b, s, cfg.q_dim), p["wo"])
        return x + out, (new_cache if mode != "train" else None)

    k = mm(h, p["wk"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = mm(h, p["wv"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if positions is None:
        base = jnp.zeros((b,), jnp.int32) if lengths is None else lengths
        positions = base[:, None] + jnp.arange(s)[None, :]
    theta = _theta_for(cfg, kind)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)

    attn_kind = {"causal": "causal", "local": "local", "full": "full"}[
        "full" if (kind == "causal" and not cfg.causal) else kind
    ]
    new_cache = None
    if mode == "train":
        out = attention_core(q, k, v, kind=attn_kind, cfg=cfg)
    elif mode == "prefill":
        assert cache is not None and lengths is not None
        new_cache = cache_write(cache, k, v, lengths, kind=attn_kind, window=cfg.window_size)
        out = attention_core(q, k, v, kind=attn_kind, cfg=cfg)
    else:  # decode
        assert cache is not None and lengths is not None
        new_cache = cache_write(cache, k, v, lengths, kind=attn_kind, window=cfg.window_size)
        out = attention_decode(q, new_cache, lengths, kind=attn_kind, window=cfg.window_size)
    out = mm(out.reshape(b, s, cfg.q_dim), p["wo"])
    return x + out, new_cache


# ---------------------------------------------------------------------------
# Dense (SwiGLU) MLP
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ArchConfig, d_ff: Optional[int] = None) -> dict:
    dt = cfg.param_dtype
    d, f = cfg.d_model, d_ff or cfg.d_ff
    specs = {
        "ln": ParamSpec((d,), ("embed",), "zeros", dtype=dt),
        "wi_up": ParamSpec((d, f), ("embed", "mlp"), "normal", dtype=dt),
        "wo": ParamSpec((f, d), ("mlp", "embed"), "normal", dtype=dt),
    }
    if cfg.mlp_gated:
        specs["wi_gate"] = ParamSpec((d, f), ("embed", "mlp"), "normal", dtype=dt)
    return specs


def mlp_apply(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    mm = functools.partial(router.matmul, out_dtype=x.dtype,
                           config=RuntimeConfig.from_arch(cfg))
    h = rms_norm(x, p["ln"])
    if cfg.mlp_gated:
        gate = shard_act(mm(h, p["wi_gate"], activation="silu"), "batch", None, "mlp")
        up = shard_act(mm(h, p["wi_up"]), "batch", None, "mlp")
        return x + mm(gate * up, p["wo"])
    up = shard_act(mm(h, p["wi_up"], activation="gelu"), "batch", None, "mlp")
    return x + mm(up, p["wo"])


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-based dispatch, EP-shardable)
# ---------------------------------------------------------------------------

def moe_specs(cfg: ArchConfig) -> dict:
    dt = cfg.param_dtype
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    specs = {
        "ln": ParamSpec((d,), ("embed",), "zeros", dtype=dt),
        "router": ParamSpec((d, e), ("embed", None), "small_normal", dtype="float32"),
        "w_gate": ParamSpec((e, d, f), ("expert", "embed", "mlp"), "normal", dtype=dt),
        "w_up": ParamSpec((e, d, f), ("expert", "embed", "mlp"), "normal", dtype=dt),
        "w_down": ParamSpec((e, f, d), ("expert", "mlp", "embed"), "normal", dtype=dt),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        specs["sh_gate"] = ParamSpec((d, fs), ("embed", "mlp"), "normal", dtype=dt)
        specs["sh_up"] = ParamSpec((d, fs), ("embed", "mlp"), "normal", dtype=dt)
        specs["sh_down"] = ParamSpec((fs, d), ("mlp", "embed"), "normal", dtype=dt)
    return specs


def moe_capacity(tokens_per_group: int, cfg: ArchConfig) -> int:
    c = int(np.ceil(tokens_per_group * cfg.experts_per_token / cfg.num_experts
                    * cfg.capacity_factor))
    return max(c, 1)


def _dispatch_indices(eidx: jax.Array, e: int, cap: int):
    """eidx: (TK,) expert id per routing entry -> (slot (TK,), keep (TK,)).
    Sort-based: position within the expert's group, capped at capacity."""
    tk = eidx.shape[0]
    order = jnp.argsort(eidx, stable=True)
    sorted_e = eidx[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))  # (E,)
    pos_in_e = jnp.arange(tk) - starts[sorted_e]
    keep_sorted = pos_in_e < cap
    slot_sorted = jnp.where(keep_sorted, sorted_e * cap + pos_in_e, e * cap)
    inv = jnp.argsort(order, stable=True)
    return slot_sorted[inv], keep_sorted[inv]


def moe_apply(p: dict, x: jax.Array, cfg: ArchConfig,
              num_groups: Optional[int] = None) -> tuple[jax.Array, jax.Array]:
    """Returns (output, load-balance aux loss)."""
    b, s, d = x.shape
    e, k_top = cfg.num_experts, cfg.experts_per_token
    g = num_groups if num_groups is not None else (b if s > 1 else max(1, min(b, 8)))
    assert (b * s) % g == 0, (b, s, g)
    t = (b * s) // g
    cap = moe_capacity(t, cfg)
    h = rms_norm(x, p["ln"])
    hg = h.reshape(g, t, d)
    logits = jnp.einsum("gtd,de->gte", hg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, top_idx = lax.top_k(probs, k_top)  # (G, T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balance aux (Switch-style); vmap'd scatter (see dispatch note below)
    density = jax.vmap(
        lambda idx: jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    )(top_idx) / (t * k_top)
    aux = e * jnp.mean(jnp.sum(density * probs.mean(axis=1), axis=-1))

    eidx = top_idx.reshape(g, t * k_top)
    slot, keep = jax.vmap(functools.partial(_dispatch_indices, e=e, cap=cap))(eidx)
    tok = jnp.arange(t * k_top) // k_top  # (TK,) token of each entry

    # NOTE: every gather/scatter below is vmap'd over the group axis — batched
    # (operand_batching_dims) indexing is what GSPMD can partition; explicit
    # arange-indexing makes the partitioner replicate the full dispatch buffer
    # on every device (hundreds of GiB for kimi-k2).
    def _dispatch_one(hg_g, slot_g, keep_g):
        src = hg_g[tok] * keep_g[:, None].astype(hg_g.dtype)  # (TK, D)
        buf = jnp.zeros((e * cap + 1, d), hg_g.dtype).at[slot_g].set(src, mode="drop")
        return buf[: e * cap]

    disp = jax.vmap(_dispatch_one)(hg, slot, keep).reshape(g, e, cap, d)
    # EP dispatch boundary: groups on the pure-DP axes, experts on the model
    # axis (an all-to-all-shaped reshard under the moe_dp_attention layout)
    disp = shard_act(disp, "batch_dp", "expert", None, None)

    gate = shard_act(jnp.einsum("gecd,edf->gecf", disp, p["w_gate"]),
                     "batch_dp", "expert", None, None)
    gate = gate * jax.nn.sigmoid(gate)  # silu
    up = shard_act(jnp.einsum("gecd,edf->gecf", disp, p["w_up"]),
                   "batch_dp", "expert", None, None)
    out_e = jnp.einsum("gecf,efd->gecd", (gate * up).astype(hg.dtype), p["w_down"])
    out_e = shard_act(out_e, "batch_dp", "expert", None, None)

    cdt = jnp.dtype(cfg.moe_combine_dtype)
    weights = (gate_vals.reshape(g, t * k_top) * keep.astype(jnp.float32)).astype(cdt)

    def _combine_one(out_g, slot_g, w_g):
        flat = jnp.concatenate([out_g.reshape(e * cap, d),
                                jnp.zeros((1, d), out_g.dtype)], axis=0)
        gathered = flat[slot_g].astype(cdt) * w_g[:, None]  # (TK, D)
        return jnp.zeros((t, d), cdt).at[tok].add(gathered)

    y = jax.vmap(_combine_one)(out_e, slot, weights)
    y = shard_act(y, "batch", None, None).astype(x.dtype)

    if cfg.num_shared_experts:
        mm = functools.partial(router.matmul, out_dtype=x.dtype,
                               config=RuntimeConfig.from_arch(cfg))
        sg = mm(hg, p["sh_gate"], activation="silu")
        su = mm(hg, p["sh_up"])
        y = y + mm(sg * su, p["sh_down"])

    return x + y.reshape(b, s, d), aux
