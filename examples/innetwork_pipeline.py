"""The paper's full working procedure, end to end (all three use-cases):

  packets -> feature extractor (meta set + series + payload memories)
          -> packet path   (use-case 1: MLP intrusion detection, latency)
          -> flow paths    (use-case 2: 1D-CNN classify; use-case 3: payload
                            transformer classify; throughput)
          -> decisions     (RV-core analogue: rule-table updates)

Also demonstrates heterogeneous collaborative computing: the CNN runs once
with Octopus routing (layer 1 -> VPE path, deep layers -> AryPE path, fused
aggregation) and once as a 'straightforwardly inserted accelerator'
(everything on the systolic path, partial blocks through memory), reporting
the throughput ratio against the paper's 1.69x.

Finally the same procedure runs as one *continuous* loop: the streaming
OctopusPipeline ingests live mice/elephant traffic microbatches, carries the
flow table across steps (donated, no retrace), classifies emitted ready flows
and feeds every decision back into one rule table — the paper's steps 1 -> 6
fused into a single jit'd step.  The tracker inside the step is the
vectorized segmented update (bit-exact to the scan oracle), and with
--scan-len N the loop dispatches N microbatches per jit call (lax.scan over
the step), amortizing host round-trips — both runs are shown side by side.

With --overlap the streaming runs use the deferred-sync runtime: run()
double-buffers (chunk k+1 is staged while chunk k executes on device) and
the traffic generator is staged by the depth-2 prefetcher — bit-identical
decisions, and the report splits each dispatch into host vs exposed-device
time.

  PYTHONPATH=src python examples/innetwork_pipeline.py [--flows 400]
      [--steps 40] [--scan-len 8] [--overlap]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import platform


def main():
    platform.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--flows", type=int, default=400)
    ap.add_argument("--steps", type=int, default=40,
                    help="streaming pipeline microbatches")
    ap.add_argument("--scan-len", type=int, default=8,
                    help="microbatches fused per dispatch (lax.scan chunk)")
    ap.add_argument("--num-shards", type=int, default=2,
                    help="hash-partitioned tracker lanes (1 disables the "
                         "sharded weak-scaling demo)")
    ap.add_argument("--overlap", action="store_true",
                    help="deferred-sync dispatch + prefetched traffic: "
                         "overlap host staging with device execution "
                         "(bit-identical decisions)")
    args = ap.parse_args()

    from repro.core.feature_extractor import ExtractorConfig, FeatureExtractor
    from repro.data.packets import PacketTraceConfig, synth_packet_trace
    from repro.models import paper_models
    from repro.runtime import RuntimeConfig
    from repro.serving.packet_path import FlowPath, PacketPath

    # ---------------------------------------------------------------- traffic
    trace_cfg = PacketTraceConfig(num_flows=args.flows, pkts_per_flow=20,
                                  seed=0, table_size=8192)
    packets, classes, hashes, labels = synth_packet_trace(trace_cfg)
    n_pkts = int(packets.ts.shape[0])
    print(f"[trace] {args.flows} flows, {n_pkts} packets")

    # ------------------------------------------------------- feature extract
    ex = FeatureExtractor(ExtractorConfig(table_size=8192, top_n=20, top_k=15))
    extract = jax.jit(ex.extract_segmented)
    jax.block_until_ready(extract(packets))  # compile outside the timing
    t0 = time.perf_counter()
    feats, series, sizes, payload, counts = jax.block_until_ready(extract(packets))
    dt = time.perf_counter() - t0
    print(f"[extract] segmented path: {n_pkts/dt/1e6:.2f} Mpkt/s "
          f"(paper FPGA: 31 Mpkt/s @125MHz)")

    # --------------------------------------------- use-case 1: packet MLP IDS
    mlp_params = paper_models.init_paper_model("mlp", jax.random.PRNGKey(0))
    ppath = PacketPath(mlp_params)
    ppath.warmup(batch=n_pkts)
    actions = ppath.process(packets)
    print(f"[usecase1] {n_pkts} pkts -> {int(actions.sum())} flagged; "
          f"batch latency {ppath.stats.latency_us:.1f} us "
          f"({ppath.stats.latency_us/n_pkts*1000:.1f} ns/pkt; paper: 207 ns)")

    # ------------------------------------------- use-case 2: flow CNN classify
    ready = np.asarray(counts) >= 20
    x_cnn = jnp.log1p(series[ready].astype(jnp.float32))
    cnn_params = paper_models.init_paper_model("cnn", jax.random.PRNGKey(1))
    fpath = FlowPath(cnn_params, model="cnn")
    print(fpath.route_plan(int(ready.sum())).explain())  # shared placement truth
    fpath.warmup(int(ready.sum()))
    fpath.process(x_cnn, np.flatnonzero(ready))
    kflow = fpath.stats.throughput / 1e3
    print(f"[usecase2] {int(ready.sum())} flows classified "
          f"({kflow:.1f} kflow/s; paper w/ collaborating: 90 kflow/s)")

    # collaborative ablation — the fusion half transfers to the CPU host
    # (block partials through memory vs fused accumulation); the routing half
    # only shows on the TPU target / cycle model (CPUs prefer dots over the
    # VPU-style mul+reduce), see benchmarks/bench_collaborative.py.
    fpath_fused = FlowPath(cnn_params, model="cnn",
                           config=RuntimeConfig(policy="arype_only"))
    fpath_off = FlowPath(cnn_params, model="cnn",
                         config=RuntimeConfig(policy="arype_only",
                                              fused_aggregation=False))
    for p_ in (fpath_fused, fpath_off):
        p_.warmup(int(ready.sum()))
        p_.process(x_cnn, np.flatnonzero(ready))
    ratio = fpath_off.stats.latency_us / fpath_fused.stats.latency_us
    print(f"[usecase2] fused-aggregation speedup {ratio:.2f}x "
          f"(paper's collaborative win: 1.69x; routing half: see cycle model)")

    # ------------------------------- use-case 3: payload transformer classify
    ready_k = np.asarray(counts) >= 15
    x_tf = payload[ready_k].astype(jnp.float32) / 255.0
    tf_params = paper_models.init_paper_model("transformer", jax.random.PRNGKey(2))
    tpath = FlowPath(tf_params, model="transformer")
    tpath.warmup(int(ready_k.sum()))
    tpath.process(x_tf, np.flatnonzero(ready_k))
    print(f"[usecase3] {int(ready_k.sum())} flows "
          f"({tpath.stats.throughput/1e3:.1f} kflow/s; paper: 35.7 kflow/s)")

    # -------------------------------------------------------------- decisions
    print(f"[decisions] rule tables: usecase1 gen={ppath.rules.generation} "
          f"({len(ppath.rules.rules)} rules), usecase2 gen={fpath.rules.generation}, "
          f"usecase3 gen={tpath.rules.generation}")

    # ------------------------------------------- streaming pipeline (steps 1-6)
    from repro.data.traffic import TrafficConfig, TrafficGenerator
    from repro.serving import OctopusPipeline, PipelineConfig

    def streaming(tracker: str, scan_len: int):
        from repro.data.traffic import prefetch

        pipe = OctopusPipeline(
            mlp_params, cnn_params,
            PipelineConfig(batch_size=64, max_ready=8, flow_model="cnn",
                           table_size=1024, tracker=tracker,
                           scan_len=scan_len, overlap=args.overlap))
        traffic = TrafficGenerator(TrafficConfig(
            batch_size=64, active_flows=32, elephant_fraction=0.3,
            table_size=1024, seed=0))
        pipe.warmup()
        # full chunks only, at least one (--steps below --scan-len must not
        # silently run nothing)
        steps = max(scan_len, args.steps - args.steps % scan_len)
        src = (prefetch(traffic.batches(steps), depth=2) if args.overlap
               else traffic)
        return pipe, pipe.run(src, steps=steps)

    # PR 3 baseline (order-exact scan tracker, one microbatch per dispatch)
    # vs the vectorized segmented tracker with chunked lax.scan dispatch —
    # identical decisions (differentially tested), different throughput
    pipe0, s0 = streaming("scan", 1)
    pipe, stats = streaming("segmented", max(1, args.scan_len))
    print(pipe.explain())  # both engines, one RoutePlan
    print(f"[pipeline] scan/x1 baseline: {s0.pkt_per_s/1e6:.3f} Mpkt/s, "
          f"{s0.flow_per_s/1e3:.2f} kflow/s over {s0.steps} microbatches")
    print(f"[pipeline] segmented/x{pipe.cfg.scan_len}: {stats.steps} microbatches "
          f"in {stats.dispatches} dispatches: {stats.packets} pkts "
          f"({stats.pkt_per_s/1e6:.3f} Mpkt/s; paper extraction: 31 Mpkt/s), "
          f"{stats.flows} ready flows classified "
          f"({stats.flow_per_s/1e3:.2f} kflow/s; paper: 90 kflow/s), "
          f"{stats.new_flows} established / {stats.evicted} evicted, "
          f"speedup {stats.pkt_per_s/max(s0.pkt_per_s, 1e-9):.2f}x")
    print(f"[pipeline] rule table: {len(pipe.rules.rules)} rules, "
          f"gen={pipe.rules.generation}, step latency {stats.step_us:.0f} us, "
          f"traces={pipe.trace_count} (no retrace after warmup)")
    if args.overlap:
        print(f"[pipeline] overlapped dispatch: host {stats.host_us:.0f} us "
              f"+ exposed device {stats.device_us:.0f} us per dispatch")

    # ------------------------------------- sharded lanes (weak scaling, §2.2)
    if args.num_shards > 1:
        from repro.serving import ShardedOctopusPipeline

        S, per_lane = args.num_shards, 64
        sharded = ShardedOctopusPipeline(
            mlp_params, cnn_params,
            PipelineConfig(batch_size=per_lane * S, max_ready=max(8, 4 * S),
                           flow_model="cnn", table_size=1024),
            num_shards=S, lane_batch=int(1.5 * per_lane))
        traffic = TrafficGenerator(TrafficConfig(
            batch_size=per_lane * S, active_flows=32 * S,
            elephant_fraction=0.3, table_size=1024, seed=0))
        sharded.warmup()
        st = sharded.run(traffic, steps=max(4, args.steps // 2))
        print(f"[sharded] {S} lanes ({sharded.backend}), per-lane load "
              f"{per_lane} pkts: {st.pkt_per_s/1e6:.3f} Mpkt/s aggregate "
              f"({st.packets} pkts, {st.padded} padded lane rows, "
              f"{st.dispatches} dispatches), {st.flows} flows classified")


if __name__ == "__main__":
    main()
