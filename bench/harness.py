"""One run of one benchmark cell: set up the served Octopus path, warm it
up, drive the cell's traffic through ``OctopusService.submit`` for the
measured window, check what it served, and report.

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic mix in ``bench/traffic/<traffic>.json``, and each metric's reader
in ``bench/metrics/<metric>.py`` (a module with ``read(run) -> float |
None``).  Adding a cell, a configuration, a mix or a metric adds files and
entries; no code here changes.
"""
from __future__ import annotations

import asyncio
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import check, models, work
from bench.reference.oracle import hot_slot
from bench.traffic import generator

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
METRICS_DIR = BENCH_DIR / "metrics"
# the step program the service dispatches (jit of OctopusPipeline._masked_step)
STEP_PROGRAM = "_masked_step"
# how long after the window an answer may still come (latency counts it)
LATE_S = 60.0
# seconds at the end of the window that a traced run records
TRACE_S = 1.0
# a traced run's host numbers leave out requests due this long before the
# profiler starts, so that none waited through its start
CLEAR_S = 0.5


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT, *, mix_dir: Path = generator.MIX_DIR
              ) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration,
    traffic mix and the metrics it reports."""
    bench = json.loads((Path(root) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((Path(root) / conf["file"]).read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, int(w["chips"]), config,
                generator.load_mix(w["traffic"], mix_dir),
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


def load_reader(metric: str, metrics_dir: Path = METRICS_DIR):
    """The ``read`` function of ``<metrics_dir>/<metric>.py``."""
    path = Path(metrics_dir) / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: list, run: dict, metrics_dir: Path = METRICS_DIR) -> dict:
    """{name: {"value", "unit"}} for each metric whose reader found
    something to read."""
    out = {}
    for m in entries:
        v = load_reader(m["name"], metrics_dir)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------- building
def build(config: dict, params: dict):
    """The pipeline and service a configuration describes (lanes go one to
    a device where there are enough devices, as the pipeline decides)."""
    from repro.runtime import RuntimeConfig
    from repro.serving import (OctopusPipeline, OctopusService, PipelineConfig,
                               ServiceConfig, ShardedOctopusPipeline)

    c = config
    pcfg = PipelineConfig(
        batch_size=c["buckets"][-1], max_ready=c["max_ready"],
        flow_model=c["flow_model"]["kind"], table_size=c["table_size"],
        top_n=c["top_n"], top_k=c["top_k"], pay_bytes=c["pay_bytes"],
        tracker=c["tracker"], cold_size=c["cold_size"],
        cold_policy=c["cold_policy"])
    rt = RuntimeConfig(use_pallas=c["use_pallas"])
    if c["lanes"] > 1:
        pipe = ShardedOctopusPipeline(params["packet"], params["flow"], pcfg,
                                      num_shards=c["lanes"], config=rt)
    else:
        pipe = OctopusPipeline(params["packet"], params["flow"], pcfg, config=rt)
    svc = OctopusService(pipe, ServiceConfig(buckets=tuple(c["buckets"]),
                                             depth_budget=c["depth_budget"]))
    return pipe, svc


def host_timed(window: list, pre_trace: dict | None) -> list:
    """The window's requests that the host-side numbers read: all of them,
    or in a traced run those due ``CLEAR_S`` or more before the profiler
    started, whose answers its start did not hold up."""
    if pre_trace is None:
        return window
    return [r for r in window if r.due < pre_trace["t"] - CLEAR_S]


class Recorder:
    """Wraps the pipeline's ``step_masked`` (the step the service
    dispatches) to keep each dispatch's kept-row count and the outputs the
    check reads, under a host span of its own.  The pipeline's own feedback
    has already read the drained mask and tuple ids, the flow classes and
    the cold counters back to the host; only the flow scores are copied
    here, asynchronously, and read a few dispatches later."""

    LAG = 4
    HOST = ("flow_cls", "spilled", "promoted")

    def __init__(self, pipe):
        import jax

        self._jax = jax
        self.pipe = pipe
        self.step = pipe.step_masked
        self.records: list[dict] = []
        pipe.step_masked = self

    def __call__(self, packets, keep):
        with self._jax.profiler.TraceAnnotation("bench.step"):
            t0 = time.perf_counter()
            out = self.step(packets, keep)
            out.flow_scores.copy_to_host_async()
            self.records.append({
                "n": int(np.asarray(keep).sum()), "t": t0,
                "dt": time.perf_counter() - t0,
                "drained": {"mask": np.asarray(out.drained.mask),
                            "tuple_id": np.asarray(out.drained.tuple_id)},
                "flow_scores": out.flow_scores,
                **{k: np.asarray(getattr(out, k)) for k in self.HOST}})
            if len(self.records) > self.LAG:
                self._to_host(self.records[-1 - self.LAG])
        return out

    @staticmethod
    def _to_host(rec: dict) -> None:
        rec["flow_scores"] = np.asarray(rec["flow_scores"])

    def finish(self) -> list[dict]:
        for rec in self.records[-self.LAG - 1:]:
            self._to_host(rec)
        self.pipe.step_masked = self.step
        return self.records


# ----------------------------------------------------------------- traffic
class Ports:
    """Per-port queues of requests cut from the generator's blocks."""

    BLOCK_REQUESTS = 16

    def __init__(self, streams: generator.Streams, request_packets: int):
        self.streams = streams
        self.n = request_packets
        self.queues = [deque() for _ in range(streams.ports)]
        self.blocks = 0

    def next(self, port: int) -> dict:
        if not self.queues[port]:
            import jax

            with jax.profiler.TraceAnnotation("bench.make_traffic"):
                self._refill()
        return self.queues[port].popleft()

    def _refill(self) -> None:
        n, k = self.n, self.BLOCK_REQUESTS
        b = self.streams.block(n * k)
        self.blocks += 1
        for p in range(self.streams.ports):
            for j in range(k):
                sl = slice(j * n, (j + 1) * n)
                self.queues[p].append(
                    {f: getattr(b, f)[p, sl] for f in generator.Block.FIELDS})


@dataclass
class Request:
    port: int
    packets: dict
    due: float
    done: float = 0.0
    result: object = None
    error: str = ""
    phase: str = "warmup"


@dataclass
class Drive:
    """State of the clients of one run."""

    svc: object
    ports: Ports
    order: list = field(default_factory=list)  # requests in submission order
    phase: str = "warmup"
    stop: bool = False
    answered_packets: int = 0

    async def submit(self, port: int, due: float) -> Request:
        from repro.serving import Rejected

        req = Request(port, self.ports.next(port), due, phase=self.phase)
        self.order.append(req)
        try:
            req.result = await self.svc.submit(_batch(req.packets), client_id=port)
            if isinstance(req.result, Rejected):
                req.error = "shed"
            else:
                self.answered_packets += req.packets["ts"].shape[0]
        except Exception as e:  # the service answered with an error
            req.error = repr(e)
        req.done = time.perf_counter()
        return req

    async def closed_client(self, port: int) -> None:
        while not self.stop:
            await self.submit(port, time.perf_counter())

    async def open_loop(self, t0: float, due, port, late: list) -> list:
        """Submit one request to ``port[i]`` at ``t0 + due[i]``, whether or
        not earlier ones were answered; returns the submit tasks and appends
        how late each submit was to ``late``."""
        tasks = []
        for t, p in zip(due, port):
            now = time.perf_counter()
            if t0 + t > now:
                await asyncio.sleep(t0 + t - now)
            late.append((time.perf_counter() - (t0 + t), t0 + t))
            tasks.append(asyncio.create_task(self.submit(int(p), t0 + t)))
        return tasks


def _batch(pk: dict):
    from repro.core.flow_tracker import PacketBatch

    return PacketBatch(**pk)


async def _drive(cell: Cell, svc, ports: Ports, seconds: float, seed: int,
                 trace_dir: str | None, phases: dict) -> dict:
    import jax

    mix = cell.mix
    lanes = int(cell.config["lanes"])
    d = Drive(svc, ports)
    await svc.start()  # compiles (or loads) every bucket's step program
    phases["compiled"] = time.perf_counter()
    warm_packets = mix["warmup_population_passes"] * cell.config["live_flows"] * lanes
    clients = [asyncio.create_task(d.closed_client(p))
               for p in range(ports.streams.ports)
               for _ in range(mix.get("outstanding_per_port", 2))]
    while d.answered_packets < warm_packets:
        await asyncio.sleep(0.01)
    late = []
    if mix["loop"] == "open":
        d.stop = True
        await asyncio.gather(*clients)
        clients = []
        n_req = round(mix["rate_pkt_per_s_per_lane"] * lanes * seconds
                      / mix["request_packets"])
        due, port = generator.arrivals(n_req, seconds, ports.streams.ports, seed)
    phases["warm"] = time.perf_counter()
    d.phase = "window"
    t0 = time.perf_counter()
    st = {"t0": t0, "svc0": _svc_snapshot(svc), "pipe0": _pipe_snapshot(svc.pipeline),
          "cpu0": time.process_time()}
    traced = None
    if trace_dir is not None:
        traced = asyncio.create_task(_trace_tail(trace_dir, t0 + seconds, svc))
    tasks = []
    if mix["loop"] == "open":
        tasks = await d.open_loop(t0, due, port, late)
    now = time.perf_counter()
    if t0 + seconds > now:
        await asyncio.sleep(t0 + seconds - now)
    st["t1"] = time.perf_counter()
    st["cpu1"] = time.process_time()
    st["svc1"] = _svc_snapshot(svc)
    st["pipe1"] = _pipe_snapshot(svc.pipeline)
    d.phase = "after"
    if traced is not None:
        span, st["t_traced"], st["pre_trace"] = await traced
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    d.stop = True
    pending = clients + tasks
    _, not_done = await asyncio.wait(pending, timeout=LATE_S) if pending else ((), ())
    await svc.stop()
    st.update(lateness=late, drive=d, unfinished=len(not_done))
    return st


async def _trace_tail(trace_dir: str, t_end: float, svc):
    """Start the profiler ``TRACE_S`` before ``t_end`` and open the span
    that marks the traced window; the caller closes both at the window's
    end.  Only the tail is traced: a step emits thousands of device events.
    Returns the span, when it opened, and the host counters just before the
    profiler started."""
    import jax

    now = time.perf_counter()
    if t_end - TRACE_S > now:
        await asyncio.sleep(t_end - TRACE_S - now)
    # the host counters' window ends here: starting the profiler stalls the host
    pre = {"t": time.perf_counter(), "svc": _svc_snapshot(svc),
           "pipe": _pipe_snapshot(svc.pipeline)}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = jax.profiler.TraceAnnotation("bench.traced")
    span.__enter__()
    return span, time.perf_counter(), pre


def _svc_snapshot(svc) -> dict:
    s = svc.stats
    return {"dispatches": s.dispatches, "padded": s.padded, "served": s.served,
            "host_s": s.host_s, "device_s": s.device_s}


def _pipe_snapshot(pipe) -> dict:
    s = pipe.stats
    return {k: getattr(s, k) for k in ("packets", "flows", "new_flows", "evicted",
                                       "spilled", "promoted", "host_s",
                                       "dispatches")}


# -------------------------------------------------------------------- run
def device_info(chips: int, require_chip: bool) -> tuple[dict, list]:
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform {devs[0].platform!r})")
    if require_chip and len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    return ({"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": chips}, devs[:chips])


def memory_peak(devices: list) -> int | None:
    peaks = []
    for dv in devices:
        st = dv.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _state_to_host(pipe, lanes: int) -> dict:
    import jax

    st = jax.device_get(pipe.state)
    add = (lambda a: np.asarray(a)) if lanes > 1 else (lambda a: np.asarray(a)[None])
    if pipe.cfg.cold_size:
        hot, cold = st.hot, st.cold
        return {"hot": {k: add(v) for k, v in hot._asdict().items()},
                "cold": {k: add(v) for k, v in cold._asdict().items()}}
    return {"hot": {k: add(v) for k, v in st._asdict().items()}, "cold": None}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True, patch=None,
             metrics_dir: Path = METRICS_DIR, control: tuple = ()) -> dict:
    """One run of ``cell``; returns the result (the printed line's keys, plus
    ``compared`` and, for the control, ``control``).

    ``patch(pipe, svc)`` may replace parts of the timed path before warm-up
    (the fault tests break it this way).  ``control`` lists controls
    (``check.answers``) to compare in the program's place as well."""
    import jax

    phases = {}
    device, devices = device_info(cell.chips, require_chip)
    phases["devices"] = time.perf_counter()
    cfg = cell.config
    lanes = int(cfg["lanes"])
    params = models.make_weights(cfg, seed, devices[0])
    pipe, svc = build(cfg, params)
    rec = Recorder(pipe)
    if patch is not None:
        patch(pipe, svc)
    streams = generator.Streams(cell.mix, lanes=lanes,
                                flows_per_lane=cfg["live_flows"], seed=seed,
                                pay_bytes=cfg["pay_bytes"])
    ports = Ports(streams, cell.mix["request_packets"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    phases["built"] = time.perf_counter()
    st = asyncio.run(_drive(cell, svc, ports, seconds, seed, trace_dir, phases))
    t0, t1 = st["t0"], st["t1"]
    d = st["drive"]
    peak = memory_peak(devices)
    records = rec.finish()
    final_state = _state_to_host(pipe, lanes)
    host_params = jax.device_get(params)
    # free the program's state before the reference runs
    rec.pipe = d.svc = None
    del pipe, svc, params

    dispatches = _dispatch_packets(d.order, records)
    # ---- what the window saw
    window = [r for r in d.order if r.phase == "window"]
    answered = [r for r in d.order if r.result is not None and not r.error]
    in_win = [r for r in answered if t0 <= r.done <= t1]
    failed = [r for r in window if r.result is None or r.error]
    s0, s1 = st["svc0"], st["svc1"]
    p0, p1 = st["pipe0"], st["pipe1"]
    # in a traced run the host-side per-layer numbers stop where tracing
    # began, and the requests they read were answered before it began
    pre = st.get("pre_trace") or {"t": t1, "svc": s1, "pipe": p1}
    timed = host_timed(window, st.get("pre_trace"))
    span = t1 - t0
    run = {
        "cell": cell.name, "config": cfg, "mix": cell.mix, "seconds": span,
        "setup_s": t0 - t_start,
        "packets_in_window": sum(r.packets["ts"].shape[0] for r in in_win),
        "flows_in_window": p1["flows"] - p0["flows"],
        # an unanswered request counts as waiting until the run gave up
        "latency_s": [(r.done - r.due) if r.result is not None and not r.error
                      else (t1 + LATE_S - r.due) for r in timed],
        "queue_wait_s": [r.result.queue_wait_s for r in timed
                         if r.result is not None and not r.error],
        "service": {k: pre["svc"][k] - s0[k] for k in s0},
        "pipeline": {k: pre["pipe"][k] - p0[k] for k in p0},
        "chips": cell.chips, "peaks": None, "trace": None,
        "work": None,
    }
    if trace:
        from bench import trace as tr

        run["peaks"] = work.peaks(device["kind"]) if require_chip else None
        t_parse = time.perf_counter()
        events = tr.load_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = tr.reduce(events, STEP_PROGRAM)
        run["trace"] = red
        phases["trace_parsed_s"] = time.perf_counter() - t_parse + t_start
        run["work"] = _window_work(cfg, [x for x in dispatches
                                         if st["t_traced"] <= x["out"]["t"] < t1])
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    device["memory_peak_bytes"] = peak

    # ---- correctness
    t_check = time.perf_counter()
    mismatch, flows = check.replay(cfg, dispatches, final_state)
    served = {"flow_cls": [x["flow_cls"] for x in records],
              "flow_scores": [x["flow_scores"] for x in records]}
    reqs = [{"packets": r.packets, "actions": np.asarray(r.result.pkt_actions)}
            for r in answered]
    notes = {}
    got = check.answers(cfg, host_params, reqs, flows, served, notes=notes)
    lim = cfg["limits"]
    compared = {"tracker_mismatches": {"value": mismatch, "limit": 0},
                "unanswered": {"value": len(failed) + st["unfinished"], "limit": 0}}
    for k in ("pkt_gap", "flow_gap", "score_err", "score_mean_err", "score_dev_mean"):
        compared[k] = {"value": got[k], "limit": lim[k]}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    result = {"correct": bool(correct), "attempted": len(window),
              "failed": len(failed)}
    entries = cell.per_layer if trace else cell.end_to_end
    result["metrics"] = read_metrics(entries, run, metrics_dir)
    result["device"] = device
    if trace and run["trace"] is not None:
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
    if control:
        result["control"] = {p: check.answers(cfg, host_params, reqs, flows,
                                              None, p) for p in control}
    result["compared"] = compared
    result["_info"] = {
        "generator_lateness_max_s": max((x for x, _ in st["lateness"]), default=0.0),
        "generator_lateness_p50_s": statistics.median(x for x, _ in st["lateness"])
        if st["lateness"] else 0.0,
        "stalls": _stalls(st["lateness"], records, t0),
        # CPU seconds the process got in the window: where the host sets the
        # pace, a slow run got less CPU or did less with it
        "cpu_s_in_window": round(st["cpu1"] - st["cpu0"], 3),
        **notes,
        "dispatches": len(records), "drained_flows": len(flows),
        "requests_answered": len(answered), "traffic_blocks": ports.blocks,
        "reference_s": round(time.perf_counter() - t_check, 3),
        "window_s": round(t1 - t0, 3),
        "setup": " ".join(f"{k}@{v - t_start:.2f}s" for k, v in phases.items()),
    }
    return result


def _stalls(lateness: list, records: list, t0: float) -> str:
    """Submits later than 20 ms (when, how late) beside the longest step
    call that overlapped each: where the host lost the time.  Kept on
    standard error while the open loop's host stalls are unexplained."""
    out = []
    for late, due in sorted(lateness, key=lambda x: -x[0])[:5]:
        if late < 0.02:
            break
        over = [r["dt"] for r in records if r["t"] < due + late and r["t"] + r["dt"] > due]
        out.append(f"{due - t0:.2f}s+{late * 1e3:.0f}ms/step{max(over, default=0) * 1e3:.0f}ms")
    return ",".join(out) or "none"


def _dispatch_packets(order: list, records: list) -> list:
    """Each dispatch's kept packets, rebuilt from the requests in the order
    the clients submitted them (the service dispatches first-in first-out
    and never splits a request no larger than its top bucket)."""
    out, i = [], 0
    answered = [r for r in order if r.result is not None and not r.error]
    for rec in records:
        parts, n = [], 0
        while n < rec["n"] and i < len(answered):
            parts.append(answered[i].packets)
            n += answered[i].packets["ts"].shape[0]
            i += 1
        pk = {f: np.concatenate([p[f] for p in parts]) if parts else
              np.zeros((0,) + ((16,) if f == "payload" else ()), np.int32)
              for f in check.FIELDS}
        out.append({"packets": pk, "out": rec})
    return out


def _window_work(cfg: dict, window: list) -> dict:
    """Needed FLOPs and bytes of the dispatches started in the window."""
    flops = bytes_ = 0.0
    packets = flows = 0
    for disp in window:
        pk, rec = disp["packets"], disp["out"]
        lane = check.shard_of(pk["tuple_hash"], cfg["lanes"])
        slots = len({(int(ln), hot_slot(int(h), cfg["table_size"]))
                     for ln, h in zip(lane, pk["tuple_hash"])})
        nflow = int(np.asarray(rec["drained"]["mask"]).sum())
        f, b = work.dispatch_work(cfg, packets=rec["n"], slots=slots, flows=nflow,
                                  cold_moves=int(rec["spilled"]) + int(rec["promoted"]))
        flops += f
        bytes_ += b
        packets += rec["n"]
        flows += nflow
    return {"flops": flops, "bytes": bytes_, "packets": packets, "flows": flows,
            "dispatches": len(window)}


def log(result: dict) -> None:
    """The compared numbers, each beside its limit, as the last lines on
    standard error."""
    info = result.get("_info", {})
    print("bench: " + " ".join(f"{k}={v}" for k, v in info.items()), file=sys.stderr)
    if "control" in result:
        print("bench: control " + json.dumps(result["control"]), file=sys.stderr)
    for k, v in result["compared"].items():
        print(f"compared {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()


def main(argv: list[str], t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="comma-separated controls to compare as well (bf16, fp8, "
                         "altered: see check.answers); not used by benchmark runs")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime import platform

    cache = platform.enable_compile_cache()
    cell = load_cell(args.workload)
    control = tuple(p for p in args.control.split(",") if p)
    print(f"bench: cell={cell.name} seed={args.seed} compile_cache={cache}",
          file=sys.stderr)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start, control=control)
    line = {k: v for k, v in result.items() if not k.startswith("_")}
    log(result)
    print(json.dumps(line))
    sys.stdout.flush()
    return 0
