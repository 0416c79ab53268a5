"""Find an open-loop cell's knee once, by a sweep of offered rates on the
chip, in a single process: the highest rate with no shedding and no queue
growing over the window.  The cell then runs at a fixed share of it, a
number written into its traffic mix; benchmark runs never search.

    python3 bench/sweep.py --workload <open-loop cell> --seed <n> \\
        --seconds <per rate> --rates <pkt/s per lane, comma-separated>

Prints one line per rate and, last, a JSON list of the rows.  A rate
counts as sustained when every request was answered, none was shed, the
answered rate is within 3% of the offered one, and the p99 latency of the
window's last third is under twice that of its first third (a queue that
grows all through the window fails that).
"""
import asyncio
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, models  # noqa: E402
from bench.traffic import generator  # noqa: E402


def _p99(x) -> float:
    return float(np.percentile(np.asarray(x), 99, method="higher")) if len(x) else float("nan")


async def sweep(cell, svc, ports, rates, seconds: float, seed: int) -> list:
    mix, lanes = cell.mix, int(cell.config["lanes"])
    d = harness.Drive(svc, ports)
    await svc.start()
    warm = mix["warmup_population_passes"] * cell.config["live_flows"] * lanes
    clients = [asyncio.create_task(d.closed_client(p)) for p in range(ports.streams.ports)
               for _ in range(2)]
    while d.answered_packets < warm:
        await asyncio.sleep(0.01)
    d.stop = True
    await asyncio.gather(*clients)
    rows = []
    for k, rate in enumerate(rates):
        n = round(rate * lanes * seconds / mix["request_packets"])
        due, port = generator.arrivals(n, seconds, ports.streams.ports, seed + k)
        d.stop, d.order, late = False, [], []
        shed0 = svc.stats.shed_requests
        t0 = time.perf_counter()
        tasks = await d.open_loop(t0, due, port, late)
        await asyncio.wait(tasks, timeout=seconds + harness.LATE_S)
        t1 = time.perf_counter()
        ok = [r for r in d.order if r.result is not None and not r.error]
        lat = [r.done - r.due for r in ok]
        third = [[r.done - r.due for r in ok if lo <= r.due - t0 < hi]
                 for lo, hi in ((0, seconds / 3), (2 * seconds / 3, seconds))]
        done_in = sum(r.packets["ts"].shape[0] for r in ok if r.done <= t0 + seconds)
        row = {"rate_per_lane": rate, "offered_pkt_per_s": rate * lanes,
               "answered_pkt_per_s": done_in / seconds, "requests": n,
               "unanswered": n - len(ok), "shed": svc.stats.shed_requests - shed0,
               "p50_ms": 1e3 * float(np.median(lat)) if lat else float("nan"),
               "p99_ms": 1e3 * _p99(lat), "p99_first_third_ms": 1e3 * _p99(third[0]),
               "p99_last_third_ms": 1e3 * _p99(third[1]),
               "late_max_ms": 1e3 * max((x for x, _ in late), default=0.0),
               "drain_s": t1 - t0 - seconds}
        row["sustained"] = bool(
            row["unanswered"] == 0 and row["shed"] == 0
            and row["answered_pkt_per_s"] >= 0.97 * row["offered_pkt_per_s"]
            and row["p99_last_third_ms"] < 2 * row["p99_first_third_ms"])
        print("sweep " + " ".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                                  for k, v in row.items()), file=sys.stderr, flush=True)
        rows.append(row)
    await svc.stop()
    return rows


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.runtime import platform

    platform.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    if cell.mix["loop"] != "open":
        print(f"sweep: {args.workload} is not an open-loop cell", file=sys.stderr)
        return 2
    _, devices = harness.device_info(cell.chips, True)
    cfg = cell.config
    pipe, svc = harness.build(cfg, models.make_weights(cfg, args.seed, devices[0]))
    streams = generator.Streams(cell.mix, lanes=int(cfg["lanes"]),
                                flows_per_lane=cfg["live_flows"], seed=args.seed,
                                pay_bytes=cfg["pay_bytes"])
    ports = harness.Ports(streams, cell.mix["request_packets"])
    rates = [float(r) for r in args.rates.split(",")]
    rows = asyncio.run(sweep(cell, svc, ports, rates, args.seconds, args.seed))
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
