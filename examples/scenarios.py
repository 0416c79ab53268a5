"""Drive the three pluggable-head scenarios end to end.

  PYTHONPATH=src python examples/scenarios.py [--steps N]

1. heavy-hitter: feature-only heads (no DL inference), top-k byte ranking
   over hot + cold residents;
2. DDoS: anomaly scores -> hysteresis deny controller -> rule table;
3. adversarial: a collision attack against the tracker path, with the
   eviction churn it costs.
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax

from repro.core import decisions
from repro.data.traffic import TrafficConfig, TrafficGenerator
from repro.models import paper_models
from repro.scenarios import (
    AdversarialScenario,
    DDoSScenario,
    HeavyHitterScenario,
    adversarial_config,
)
from repro.runtime import platform
from repro.serving import OctopusPipeline, PipelineConfig


def heavy_hitter(steps: int) -> None:
    sc = HeavyHitterScenario(k=5, batch_size=64, max_ready=8, table_size=256,
                             cold_size=512, top_n=8, top_k=4, pay_bytes=4)
    gen = TrafficGenerator(TrafficConfig(
        batch_size=64, active_flows=384, table_size=256, collision_free=False,
        elephant_fraction=0.3, pay_bytes=4, seed=7))
    sc.run(gen, steps)
    s = sc.pipe.stats
    print(f"[heavy-hitter] {steps} steps  pkt/s={s.pkt_per_s:.0f}  "
          f"spilled={s.spilled} promoted={s.promoted}")
    for rank, (fid, size) in enumerate(sc.top_k(), start=1):
        print(f"  #{rank}  flow {fid & 0xFFFFFFFF:#010x}  {size} bytes")


def ddos(steps: int) -> None:
    import numpy as np

    def traffic():
        return TrafficGenerator(TrafficConfig(
            batch_size=64, active_flows=16, table_size=1024,
            elephant_fraction=1.0, elephant_pkts=(30, 60), seed=3))

    # calibrate the hysteresis band from observed score quantiles (scores are
    # controller-independent, so the probe stream is the real stream)
    probe = DDoSScenario(deny_on=0.99, deny_off=0.0, batch_size=64,
                         table_size=1024)
    probe.run(traffic(), steps)
    scores = np.array([s for _, s in probe.emissions])
    on, off = (float(q) for q in np.quantile(scores, [0.6, 0.4]))
    sc = DDoSScenario(deny_on=on, deny_off=off, batch_size=64,
                      table_size=1024)
    sc.run(traffic(), steps)
    print(f"[ddos] {steps} steps  emissions={len(sc.emissions)}  "
          f"denied={len(sc.denied)}  churn={sc.churn} (raw {sc.churn_raw})")
    for fid in sorted(sc.denied)[:5]:
        rule = sc.pipe.rules.lookup(fid)
        print(f"  flow {fid & 0xFFFFFFFF:#010x}  action={rule['action']}  "
              f"generation={rule['generation']}")


def adversarial(steps: int) -> None:
    cfg = PipelineConfig(batch_size=64, max_ready=8, table_size=256,
                         top_n=8, top_k=1, pay_bytes=4,
                         pkt_head=decisions.PassHead(),
                         flow_head=decisions.TopKHead())
    pipe = OctopusPipeline(
        paper_models.init_paper_model("mlp", jax.random.PRNGKey(0)),
        paper_models.init_paper_model("cnn", jax.random.PRNGKey(1)), cfg)
    sc = AdversarialScenario(pipe, adversarial_config(
        "collision_attack", batch_size=64, table_size=256, adv_slots=4,
        active_flows=32, pay_bytes=4, seed=0))
    stats = sc.run(steps)
    print(f"[adversarial:{sc.mode}] {steps} steps  "
          f"pkt/s={stats.pkt_per_s:.0f}  evicted={stats.evicted}  "
          f"new_flows={stats.new_flows}  (population confined to 4 slots)")


def main(argv=None) -> int:
    platform.enable_compile_cache()
    ap = argparse.ArgumentParser(description="scenario family demo")
    ap.add_argument("--steps", type=int, default=16)
    args = ap.parse_args(argv)
    heavy_hitter(args.steps)
    ddos(args.steps)
    adversarial(args.steps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
