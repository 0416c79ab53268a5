"""Multi-client serving demo: four concurrent seeded traffic clients drive
one OctopusService over the streaming pipeline.

Each client is an independent closed-loop arrival process (its own seed,
microbatch size, and mice/elephant mix — think four switch ports with very
different traffic), submitting packet microbatches and awaiting verdicts.
The service coalesces whatever is queued, pads to the nearest pre-warmed
bucket (masked rows — bit-exact to unpadded serving), dispatches one
fixed-shape step, and slices the verdicts back per client.

The run prints the coalescing/padding economics and a per-client p50/p99
latency table, and asserts the acceptance property: ``trace_count`` stays
flat across the whole ragged multi-client run — startup pre-warming covered
every shape the service will ever dispatch.

  PYTHONPATH=src python examples/serve_traffic.py [--requests 16]
      [--buckets 32,64,128] [--admission shed|block] [--num-shards 0]
"""
import argparse
import asyncio
import sys

sys.path.insert(0, "src")

import jax

from repro.runtime import platform


def main():
    platform.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16,
                    help="closed-loop microbatches per client")
    ap.add_argument("--buckets", default="32,64,128",
                    help="pre-warmed batch buckets, comma-separated")
    ap.add_argument("--admission", default="shed", choices=("shed", "block"))
    ap.add_argument("--depth-budget", type=int, default=1024,
                    help="max queued packets before admission control")
    ap.add_argument("--num-shards", type=int, default=0,
                    help="hash-partitioned tracker lanes (0 = single lane)")
    args = ap.parse_args()

    from repro.data.traffic import TrafficConfig, TrafficGenerator
    from repro.models import paper_models
    from repro.serving import (
        OctopusPipeline,
        OctopusService,
        PipelineConfig,
        Rejected,
        ServiceConfig,
        ShardedOctopusPipeline,
        serve_stream,
    )

    buckets = tuple(int(b) for b in args.buckets.split(","))

    # Four ports, four very different arrival processes: staggered microbatch
    # sizes and mixes so the coalescer earns its keep.
    client_cfgs = [
        TrafficConfig(batch_size=12, elephant_fraction=0.05,  # mice port
                      active_flows=16, table_size=512, seed=101, client_id=0),
        TrafficConfig(batch_size=24, elephant_fraction=0.5,  # elephant port
                      active_flows=16, table_size=512, seed=202, client_id=1),
        TrafficConfig(batch_size=7, elephant_fraction=0.125,  # trickle port
                      active_flows=16, table_size=512, seed=303, client_id=2),
        TrafficConfig(batch_size=40, elephant_fraction=0.3,  # bursty port
                      active_flows=16, table_size=512, seed=404, client_id=3),
    ]
    gens = [TrafficGenerator(c) for c in client_cfgs]

    pipe_cfg = PipelineConfig(batch_size=buckets[-1], max_ready=8,
                              flow_model="cnn", table_size=512,
                              tracker="segmented")
    pkt_params = paper_models.init_paper_model("mlp", jax.random.PRNGKey(0))
    flow_params = paper_models.init_paper_model("cnn", jax.random.PRNGKey(1))
    if args.num_shards > 1:
        pipe = ShardedOctopusPipeline(pkt_params, flow_params, pipe_cfg,
                                      num_shards=args.num_shards)
    else:
        pipe = OctopusPipeline(pkt_params, flow_params, pipe_cfg)

    svc_cfg = ServiceConfig(buckets=buckets, admission=args.admission,
                            depth_budget=args.depth_budget)

    async def drive():
        async with OctopusService(pipe, svc_cfg) as svc:
            warm = svc.trace_count
            print(f"[warmup] {len(buckets)} buckets {buckets} pre-compiled, "
                  f"trace_count={warm}")
            outs = await asyncio.gather(*(
                serve_stream(svc, g, requests=args.requests) for g in gens))
            return svc, warm, outs

    svc, warm, outs = asyncio.run(drive())
    s = svc.stats

    shed = sum(1 for per in outs for o in per if isinstance(o, Rejected))
    print(f"[service] {s.served_requests} requests served"
          + (f", {s.shed_requests} shed" if shed else "")
          + f": {s.served} pkts in {s.dispatches} dispatches "
          f"({s.coalesced} requests coalesced, {s.padded} pad rows, "
          f"{s.pkt_per_s:.0f} pkt/s)")
    print(f"[service] queue depth high-water {s.depth_hwm} pkts "
          f"(budget {svc.cfg.depth_budget}), buffer pool "
          f"{s.pool_hits} hits / {s.pool_misses} misses")

    print(f"{'client':>6} {'batch':>5} {'reqs':>5} {'pkts':>6} "
          f"{'wait p50':>9} {'wait p99':>9} {'e2e p50':>9} {'e2e p99':>9}")
    for cfg in client_cfgs:
        c = s.clients[cfg.client_id]
        print(f"{cfg.client_id:>6} {cfg.batch_size:>5} {c.requests:>5} "
              f"{c.served:>6} {c.wait.p50:>7.0f}us {c.wait.p99:>7.0f}us "
              f"{c.e2e.p50:>7.0f}us {c.e2e.p99:>7.0f}us")
    print(f"{'all':>6} {'':>5} {s.requests:>5} {s.served:>6} "
          f"{s.wait.p50:>7.0f}us {s.wait.p99:>7.0f}us "
          f"{s.e2e.p50:>7.0f}us {s.e2e.p99:>7.0f}us")

    retraces = svc.trace_count - warm
    print(f"[service] retraces after warmup: {retraces}")
    assert retraces == 0, "ragged multi-client serving must never retrace"


if __name__ == "__main__":
    main()
