"""Per-kernel benchmark: correctness (vs oracle) + XLA-path timing + the
kernel's roofline terms on the TPU target (analytic: the container is CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.launch.mesh import HBM_BW, PEAK_FLOPS_BF16
from repro.runtime import RuntimeConfig


def run() -> list[str]:
    rows = []
    interp = RuntimeConfig().interpret
    from repro.kernels.arype_matmul import arype_matmul, ref_matmul

    for m, k, n in [(1024, 1024, 1024), (4096, 512, 2048)]:
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
        err = float(jnp.abs(arype_matmul(x, w, interpret=interp) - ref_matmul(x, w)).max())
        t = time_fn(jax.jit(lambda a, b: a @ b), x, w)
        flops = 2 * m * k * n
        byts = (m * k + k * n + m * n) * 2  # bf16 target
        ci = flops / byts
        rows.append(row(
            f"arype_matmul_{m}x{k}x{n}", t * 1e6,
            f"max_err={err:.1e};tpu_compute_us={flops/PEAK_FLOPS_BF16*1e6:.2f};"
            f"tpu_mem_us={byts/HBM_BW*1e6:.2f};arith_intensity={ci:.0f}"))

    from repro.kernels.vpe_smallmm import ref_vpe_matmul, vpe_matmul

    x = jax.random.normal(jax.random.PRNGKey(0), (20000, 3), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 32), jnp.float32)
    err = float(jnp.abs(vpe_matmul(x, w, interpret=interp) - ref_vpe_matmul(x, w)).max())
    t = time_fn(jax.jit(lambda a, b: (a[:, :, None] * b[None]).sum(1)), x, w)
    rows.append(row("vpe_smallmm_20000x3x32", t * 1e6,
                    f"max_err={err:.1e};note=paper_cnn_layer1_f1000"))

    from repro.kernels.flash_attention import flash_attention, ref_attention

    b, h, s, d = 1, 4, 512, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, h, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, h, s, d), jnp.float32)
    out = flash_attention(q, k, v, mask="causal", interpret=interp)
    ref = ref_attention(q.reshape(b * h, s, d), k.reshape(b * h, s, d),
                        v.reshape(b * h, s, d), mask="causal")
    err = float(jnp.abs(out.reshape(b * h, s, d) - ref).max())
    flops = 4 * b * h * s * s * d / 2  # causal
    rows.append(row("flash_attention_512", 0.0,
                    f"max_err={err:.1e};tpu_compute_us={flops/PEAK_FLOPS_BF16*1e6:.3f}"))

    from repro.kernels.flow_features import flow_feature_update, ref_flow_feature_update
    from repro.kernels.flow_features.ops import META_WIDTH, default_program

    rng = np.random.default_rng(0)
    slots = jnp.asarray(rng.integers(0, 8190, 4096), jnp.int32)
    meta = jnp.asarray(rng.integers(0, 1000, (4096, META_WIDTH)), jnp.int32)
    init = jnp.zeros((8192, 16), jnp.int32)
    prog = default_program()
    outk = flow_feature_update(prog, slots, meta, init, interpret=interp)
    refk = ref_flow_feature_update(prog, slots, meta, init)
    eq = bool(jnp.all(outk == refk))
    rows.append(row("flow_features_4096pkts", 0.0, f"exact_match={eq}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
