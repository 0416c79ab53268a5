"""Feature extractor throughput (paper §4.1: 31 Mpkt/s at 125 MHz, ~124 Gbps
at 500 B packets).

Two execution modes benchmarked on packets from the synthetic trace:
  * scan (order-exact oracle — the FPGA's serial line-rate semantics)
  * segmented (TPU-parallel: sort + segment reductions across all flows)
The segmented path is the hardware adaptation that buys back parallelism on
batch-oriented hardware.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import row, time_fn
from repro.core.feature_extractor import ExtractorConfig, FeatureExtractor
from repro.data.packets import PacketTraceConfig, synth_packet_trace
from repro.runtime import RuntimeConfig


def run() -> list[str]:
    rows = []
    cfg = PacketTraceConfig(num_flows=400, pkts_per_flow=20, seed=0, table_size=8192)
    packets, *_ = synth_packet_trace(cfg)
    n = int(packets.ts.shape[0])
    ex = FeatureExtractor(ExtractorConfig(table_size=8192, top_n=20))

    scan_fn = jax.jit(lambda st, p: ex.extract_scan(st, p)[0].features)
    st0 = ex.init_state()
    t_scan = time_fn(scan_fn, st0, packets, warmup=1, iters=3)
    rows.append(row("feature_extractor_scan", t_scan * 1e6,
                    f"mpkt_s={n/t_scan/1e6:.3f};paper_mpkt_s=31"))

    seg_fn = jax.jit(lambda p: ex.extract_segmented(p)[0])
    t_seg = time_fn(seg_fn, packets, warmup=1, iters=5)
    gbps = n * 500 * 8 / t_seg / 1e9
    rows.append(row("feature_extractor_segmented", t_seg * 1e6,
                    f"mpkt_s={n/t_seg/1e6:.3f};gbps_at_500B={gbps:.1f};paper_gbps=124"))

    from repro.kernels.flow_features.ops import default_program, flow_feature_update
    from repro.core.flow_tracker import hash_slot, build_meta

    slots = hash_slot(packets.tuple_hash, 8192)
    meta = jax.vmap(lambda i: build_meta(
        jax.tree.map(lambda x: x[i], packets), jnp.int32(0)))(jnp.arange(n))
    init = jnp.zeros((8192, 16), jnp.int32)
    prog = default_program()
    interp = RuntimeConfig().interpret
    kern_fn = jax.jit(lambda s, m, st: flow_feature_update(prog, s, m, st, block=256,
                                                           interpret=interp))
    t_kern = time_fn(kern_fn, slots, meta, init, warmup=1, iters=2)
    rows.append(row("feature_extractor_pallas_interpret", t_kern * 1e6,
                    f"mpkt_s={n/t_kern/1e6:.3f};note=interpret-mode-correctness-only"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
