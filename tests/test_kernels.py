"""Per-kernel correctness: shape/dtype sweeps + hypothesis, all against the
pure-jnp oracles, in interpret mode (CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels.arype_matmul import arype_matmul, arype_matmul_unfused, ref_matmul
from repro.kernels.flash_attention import flash_attention, ref_attention
from repro.kernels.flow_features import flow_feature_update, ref_flow_feature_update
from repro.kernels.flow_features.flow_features import apply_alu_program
from repro.kernels.flow_features.ops import META_WIDTH, default_program
from repro.kernels.vpe_smallmm import ref_vpe_matmul, vpe_matmul


# ---------------------------------------------------------------- arype_matmul

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (100, 200, 300), (8, 512, 64),
                                   (257, 129, 65), (16, 16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["none", "relu", "silu"])
def test_arype_matmul_sweep(m, k, n, dtype, act):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 1000 + k + n))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), dtype)
    out = arype_matmul(x, w, activation=act, interpret=True)
    ref = ref_matmul(x, w, activation=act)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * 8)


def test_arype_unfused_matches_fused():
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 384), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (384, 160), jnp.float32)
    a = arype_matmul(x, w, interpret=True)
    b = arype_matmul_unfused(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------- vpe_smallmm

@pytest.mark.parametrize("m,k,n", [(1000, 3, 32), (7, 16, 8), (4096, 6, 12), (33, 1, 2)])
@pytest.mark.parametrize("act", ["none", "relu"])
def test_vpe_matmul_sweep(m, k, n, act):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k * 7 + n))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    out = vpe_matmul(x, w, activation=act, interpret=True)
    ref = ref_vpe_matmul(x, w, activation=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- flash_attention

@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,mask,win", [
    (2, 4, 2, 256, 256, 32, "causal", 0),
    (1, 4, 1, 128, 384, 16, "full", 0),
    (2, 2, 2, 300, 300, 32, "local", 64),
    (1, 8, 4, 256, 512, 64, "causal", 0),
    (1, 2, 2, 64, 64, 128, "local", 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, sq, sk, d, mask, win, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * 7 + sq), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, d), dtype)
    out = flash_attention(q, k, v, mask=mask, window=win, interpret=True)
    g = hq // hkv
    kr = jnp.repeat(k, g, 1).reshape(b * hq, sk, d)
    vr = jnp.repeat(v, g, 1).reshape(b * hq, sk, d)
    ref = ref_attention(q.reshape(b * hq, sq, d), kr, vr, mask=mask, window=win)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out.reshape(b * hq, sq, d), np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(
    sq=st.integers(17, 200), sk=st.integers(17, 200), d=st.sampled_from([8, 16, 32]),
    mask=st.sampled_from(["causal", "full", "local"]),
)
def test_flash_attention_property(sq, sk, d, mask):
    ks = jax.random.split(jax.random.PRNGKey(sq * 211 + sk), 3)
    q = jax.random.normal(ks[0], (1, 2, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, sk, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, sk, d), jnp.float32)
    out = flash_attention(q, k, v, mask=mask, window=13, bq=32, bk=32,
                          interpret=True)
    ref = ref_attention(q.reshape(2, sq, d), k.reshape(2, sk, d), v.reshape(2, sk, d),
                        mask=mask, window=13)
    np.testing.assert_allclose(np.asarray(out.reshape(2, sq, d)), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------- flow_features

def _random_packets(rng, p, f, meta_range=1000):
    slots = jnp.asarray(rng.integers(0, f - 1, p), jnp.int32)
    meta = jnp.asarray(rng.integers(0, meta_range, (p, META_WIDTH)), jnp.int32)
    return slots, meta


@pytest.mark.parametrize("p,f,block", [(256, 32, 64), (512, 128, 256), (100, 8, 32)])
def test_flow_features_sweep(p, f, block, rng):
    slots, meta = _random_packets(rng, p, f)
    init = jnp.zeros((f, 16), jnp.int32).at[:, 4].set(2**30).at[:, 6].set(2**30)
    prog = default_program()
    out = flow_feature_update(prog, slots, meta, init, block=block, interpret=True)
    ref = ref_flow_feature_update(prog, slots, meta, init)
    assert bool(jnp.all(out == ref))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), ops=st.lists(st.integers(0, 6), min_size=16, max_size=16))
def test_alu_program_property(seed, ops):
    """A random micro-op program produces identical results through the Pallas
    kernel and the scan oracle."""
    rng = np.random.default_rng(seed)
    prog = np.stack([np.asarray(ops, np.int32),
                     rng.integers(0, META_WIDTH, 16).astype(np.int32),
                     rng.integers(0, 16, 16).astype(np.int32)], axis=1)
    prog = jnp.asarray(prog)
    slots = jnp.asarray(rng.integers(0, 7, 64), jnp.int32)
    meta = jnp.asarray(rng.integers(-50, 50, (64, META_WIDTH)), jnp.int32)
    init = jnp.asarray(rng.integers(-5, 5, (8, 16)), jnp.int32)
    out = flow_feature_update(prog, slots, meta, init, block=32, interpret=True)
    ref = ref_flow_feature_update(prog, slots, meta, init)
    assert bool(jnp.all(out == ref))


def test_alu_single_ops():
    meta = jnp.arange(META_WIDTH, dtype=jnp.int32) * 10
    hist = jnp.arange(16, dtype=jnp.int32)
    prog = jnp.asarray([[2, 1, 0]] + [[0, 0, i] for i in range(1, 16)], jnp.int32)
    out = apply_alu_program(prog, meta, hist)
    assert out[0] == hist[0] + meta[1]
    assert bool(jnp.all(out[1:] == hist[1:]))
