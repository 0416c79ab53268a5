"""One traced run of a benchmark cell, read by the program's own names.

It runs the cell through ``bench/harness.py`` as ``bench/run.py --trace 1``
does, with ``bench/scopes.py``'s ``flatten`` in place of ``trace.flatten``
(so the program's ``octopus.*`` host spans are kept) and the pipeline's and
service's span tables and counters snapshot beside the harness's own.  The
result line adds ``scopes``: device seconds per ``jax.named_scope`` (by
each operation's scope with and without the scope of its user), the
operations that took their user's, idle gaps named by the innermost
program span, the span tables of the window before the profiler started
and while it ran, and the pipeline counters before it started.
``chip_busy`` gives each device plane's busy share of the traced window and
the spread between the busiest and the idlest chip.  A cell with lanes adds
``lanes``: their fill (kept rows over dispatched lane rows), their skew
(lanes times the fullest lane's kept rows over the kept rows, 1.0 when
even), and ms per dispatch of the ``lanes.merge`` scope on the device and
of the ``octopus.partition`` and ``octopus.scatter`` host spans.

    python3 tools/scope_trace.py --workload ids-cnn.churn.sat --seed 7 --seconds 30

``--keep out.json.gz`` also writes the window's first three step programs'
events, nested operations pruned (the recorded fixture of
``tests/bench/test_bench_scopes.py`` was made so).
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, scopes, trace  # noqa: E402

# pipeline counters read beside the harness's (0 where the program lacks them)
COUNTERS = ("dispatches", "fallback_dispatches", "fallback_slots", "ready_left",
            "spilled", "promoted", "cold_walk", "packets", "padded", "lane_rows_max",
            "host_reads")
# the sharded step's merge across lanes (serving/sharded.py)
MERGE_SCOPE = "lanes.merge"
KEEP_DISPATCHES = 3


def span_delta(a: dict, b: dict) -> dict:
    """{name: [count, total_s]} of the spans recorded between two
    ``stats.spans`` snapshots."""
    return {k: [v.count - a[k].count if k in a else v.count,
                v.total_s - a[k].total_s if k in a else v.total_s]
            for k, v in b.items()
            if v.count != (a[k].count if k in a else 0)}


def prune(events: list[dict], t0: int, t1: int) -> list[dict]:
    """The events of [t0, t1) without the device operations that lie inside
    an earlier one of the same scope and own scope (a ``while`` body's
    operations), which change no scope's union; the window span cut to
    [t0, t1); operation names shortened to ``trace.op_label``."""
    out, last_end = [], {}
    for e in sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        s, f = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if e["name"] == trace.WINDOW_SPAN or f <= t0 or s >= t1:
            continue
        if e["line"] == trace.OPS_LINE:
            key = (e["plane"], scopes.top_scope(e.get("scope", "")),
                   scopes.top_scope(e.get("own_scope", "")))
            if f <= last_end.get(key, -1):
                continue
            last_end[key] = f
            e = {**e, "name": trace.op_label(e["name"])}
        out.append(e)
    host = next(e["plane"] for e in events if e["name"] == trace.WINDOW_SPAN)
    out.append({"plane": host, "line": "python3", "name": trace.WINDOW_SPAN,
                "start_ns": t0, "dur_ns": t1 - t0})
    return out


def chip_busy(events: list[dict], t0: int, t1: int) -> dict:
    """{"planes": {device plane: busy % of [t0, t1)}, "spread": busiest
    minus idlest, in points}."""
    busy = {p: 100.0 * sum(f - s for s, f in trace.busy_intervals(events, p, t0, t1))
            / (t1 - t0) for p in trace.device_planes(events)}
    return {"planes": busy,
            "spread": max(busy.values()) - min(busy.values()) if busy else None}


def named_seconds(events: list[dict], t0: int, t1: int, name: str) -> float:
    """Device seconds of the operations whose own ``op_name`` path holds the
    scope ``name``: per plane the union of their intervals in [t0, t1),
    averaged over the planes."""
    planes = trace.device_planes(events)
    iv: dict[str, list] = {p: [] for p in planes}
    for e in events:
        if e["line"] == trace.OPS_LINE and name in e.get("own_scope", "").split("/"):
            s, f = max(e["start_ns"], t0), min(e["start_ns"] + e["dur_ns"], t1)
            if f > s:
                iv[e["plane"]].append((s, f))
    tot = sum(f - s for spans in iv.values() for s, f in trace._union(spans))
    return tot / max(len(planes), 1) / 1e9


def lane_numbers(red: dict, lanes: int, merge_s: float) -> dict:
    """The lanes' fill, skew and per-dispatch costs (module docstring), from
    the counters and span tables of the window before the profiler started
    and, for the merge, the traced device seconds; ``None`` where the
    program lacks the counter."""
    c, spans = red["counters_untraced"], red["spans_untraced"]
    n, kept = c["dispatches"], c["packets"]

    def per_dispatch(span_name):
        return 1e3 * spans[span_name][1] / n if span_name in spans and n else 0.0

    return {
        "lane_fill": 100.0 * kept / (kept + c["padded"]) if kept else None,
        "lane_skew": lanes * c["lane_rows_max"] / kept
        if kept and c["lane_rows_max"] else None,
        "merge_ms_per_dispatch": 1e3 * merge_s / red["dispatches"]
        if red["dispatches"] else None,
        "partition_ms_per_dispatch": per_dispatch("octopus.partition"),
        "scatter_ms_per_dispatch": per_dispatch("octopus.scatter"),
    }


def step_op_names(pipe) -> dict:
    """{bucket: ``scopes.op_names``} of every bucket's compiled step program
    (the jit's own lowering, so the compile cache has it)."""
    import jax.numpy as jnp

    names = {}
    for b in sorted(pipe._warm_buckets):
        if hasattr(pipe, "_zero_parts"):  # lanes: (states, shards, keep, src)
            zb = pipe._zero_parts(b)
            args = (zb.shards, zb.keep, zb.src)
        else:
            args = (pipe._zero_batch(b), jnp.zeros((b,), bool))
        names[b] = scopes.op_names(
            pipe._masked_fn.lower(pipe.state, *args).compile().as_text())
    return names


def traced_run(cell, seed: int, seconds: float, *, t_start: float,
               keep: str = "", require_chip: bool = True) -> dict:
    """One traced run of ``cell``; the harness's result with ``scopes``
    added (see the module's docstring)."""
    got: dict = {"snaps": [], "svc": []}
    saved = trace.flatten, trace.reduce
    snaps0 = harness._pipe_snapshot, harness._svc_snapshot

    def reduce_and_keep(events, step_program):
        got["events"] = events
        return saved[1](events, step_program)

    def pipe_snapshot(pipe):
        s = pipe.stats
        got["snaps"].append({"spans": dict(getattr(s, "spans", {})),
                             **{k: getattr(s, k, 0) for k in COUNTERS}})
        return snaps0[0](pipe)

    def svc_snapshot(svc):
        got["svc"].append(dict(getattr(svc.stats, "spans", {})))
        return snaps0[1](svc)

    def grab(pipe, svc):
        got["pipe"] = pipe

    trace.flatten, trace.reduce = scopes.flatten, reduce_and_keep
    harness._pipe_snapshot, harness._svc_snapshot = pipe_snapshot, svc_snapshot
    try:
        result = harness.run_cell(cell, seed, seconds, True, t_start=t_start,
                                  require_chip=require_chip, patch=grab)
    finally:
        trace.flatten, trace.reduce = saved
        harness._pipe_snapshot, harness._svc_snapshot = snaps0
    events = scopes.label(got["events"], step_op_names(got.pop("pipe")))
    red = scopes.reduce(events, harness.STEP_PROGRAM)
    # the harness snapshots at the window's start, the profiler's start and
    # the window's end
    (s0, s1, s2), (v0, v1, v2) = got["snaps"][:3], got["svc"][:3]
    red["spans_untraced"] = {**span_delta(v0, v1),
                             **span_delta(s0["spans"], s1["spans"])}
    red["spans_traced"] = {**span_delta(v1, v2),
                           **span_delta(s1["spans"], s2["spans"])}
    red["counters_untraced"] = {k: s1[k] - s0[k] for k in COUNTERS}
    t0, t1 = trace.window(events)
    red["chip_busy"] = chip_busy(events, t0, t1)
    if cell.config["lanes"] > 1:
        red["lanes"] = lane_numbers(red, cell.config["lanes"],
                                    named_seconds(events, t0, t1, MERGE_SCOPE))
    if keep:
        mods = sorted(e["start_ns"] for e in events
                      if e["line"] == trace.MODULES_LINE
                      and harness.STEP_PROGRAM in e["name"] and e["start_ns"] >= t0)
        if len(mods) > KEEP_DISPATCHES:
            a, b = mods[0] - 1_000_000, mods[KEEP_DISPATCHES] - 1
            kept = prune(events, a, b)
            with gzip.open(keep, "wt") as fh:
                json.dump(kept, fh)
            red["kept"] = {"events": len(kept), "window_ns": b - a,
                           "reduced": scopes.reduce(kept, harness.STEP_PROGRAM)}
    result["scopes"] = red
    return result


def main(argv: list[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default="",
                    help="write the pruned events of the window's first "
                         "three step programs here (.json.gz)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.runtime import platform

    platform.enable_compile_cache()
    result = traced_run(harness.load_cell(args.workload), args.seed,
                        args.seconds, t_start=t_start, keep=args.keep)
    harness.log(result)
    print(json.dumps({k: v for k, v in result.items() if not k.startswith("_")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], time.perf_counter()))
