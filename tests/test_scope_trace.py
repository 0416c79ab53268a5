"""``tools/scope_trace.py``: the pruning of a kept trace, the per-chip and
lane numbers on hand-made events and counters, and one traced run of a
benchmark cell at a few hundred flows on the CPU through the harness with
the tool's hooks in place (so a change to the harness that breaks them
shows here)."""
import copy
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, scopes, trace  # noqa: E402
from tools import scope_trace  # noqa: E402

D, H = "/device:TPU:0", "/host:CPU"
M = "jit(_masked_step)/"


def ev(plane, line, name, start, dur, scope=None, own=None):
    e = {"plane": plane, "line": line, "name": name, "start_ns": start,
         "dur_ns": dur}
    if scope is not None:
        e["scope"], e["own_scope"] = scope, scope if own is None else own
    return e


def test_prune_keeps_every_scope_union():
    events = [
        ev(H, "python3", "bench.traced", 0, 2000),
        ev(D, "XLA Ops", "%while.1 = s32[] while(s32[] %x)", 100, 300,
           M + "track.spill/while"),
        ev(D, "XLA Ops", "fusion.2", 150, 50, M + "track.spill/while/body"),
        # a copy of the spill's that names no scope of its own: kept, so the
        # own scopes' union is kept too
        ev(D, "XLA Ops", "copy.5", 160, 20, M + "track.spill/while", own=""),
        ev(D, "XLA Ops", "fusion.3", 200, 50, M + "drain/x"),  # other scope: kept
        ev(D, "XLA Ops", "fusion.4", 1500, 50, M + "drain/x"),  # outside [0,1000)
        ev(H, "python3", "octopus.wait", 90, 400),
    ]
    kept = scope_trace.prune(events, 0, 1000)
    names = [e["name"] for e in kept]
    assert names == ["octopus.wait", "%while.1 while", "copy.5", "fusion.3",
                     "bench.traced"]
    assert trace.window(kept) == (0, 1000)
    for key in ("scope", "own_scope"):
        assert (scopes.scope_seconds(kept, 0, 1000, key)
                == scopes.scope_seconds(events, 0, 1000, key))


def tiny_cell():
    """``ids-cnn.churn.sat`` at a few hundred flows: same models and mix."""
    cell = harness.load_cell("ids-cnn.churn.sat")
    c = copy.deepcopy(cell.config)
    c.update(table_size=64, cold_size=512, live_flows=256, max_ready=4,
             buckets=[32], depth_budget=4096)
    m = copy.deepcopy(cell.mix)
    m.update(ports_per_lane=2, request_packets=8)
    cell.config, cell.mix = c, m
    return cell


def test_traced_run_through_the_harness_on_the_cpu(monkeypatch):
    seen = {}
    reduce = scopes.reduce

    def spy(events, step_program):
        seen["events"] = events
        return reduce(events, step_program)

    monkeypatch.setattr(scopes, "reduce", spy)
    hooks = (trace.flatten, trace.reduce, harness._pipe_snapshot,
             harness._svc_snapshot)
    r = scope_trace.traced_run(tiny_cell(), 2**31 + 11, 1.5,
                               t_start=time.perf_counter(), require_chip=False)
    assert r["correct"], r["compared"]
    assert (trace.flatten, trace.reduce, harness._pipe_snapshot,
            harness._svc_snapshot) == hooks
    # the program's spans reached the reductions, with their arguments
    steps = [e for e in seen["events"] if e["name"] == "octopus.step"]
    assert steps and all(set(e["args"]) == {"dispatch", "bucket"} for e in steps)
    assert any(e["name"] == trace.WINDOW_SPAN for e in seen["events"])
    sc = r["scopes"]
    # the profiler ran over the window's last second only; a snapshot may
    # fall inside a dispatch on the executor thread
    for k in ("spans_untraced", "spans_traced"):
        assert sc[k]["octopus.step"][0] > 0
        assert abs(sc[k]["octopus.pack"][0] - sc[k]["octopus.step"][0]) <= 1
    n = sc["counters_untraced"]["dispatches"]
    assert abs(n - sc["spans_untraced"]["octopus.step"][0]) <= 1
    assert 0 <= sc["counters_untraced"]["fallback_dispatches"] <= n
    # no device planes on the CPU: nothing for the device reductions
    assert sc["dispatches"] == 0 and sc["device_scopes"] == {}
    assert sc["chip_busy"] == {"planes": {}, "spread": None}
    assert "kept" not in sc and "lanes" not in sc  # one lane: no lane numbers


def test_chip_busy_per_plane_and_spread():
    d1 = "/device:TPU:1"
    events = [ev(D, "XLA Ops", "a", 0, 600), ev(D, "XLA Ops", "b", 300, 500),
              ev(d1, "XLA Ops", "c", 100, 200), ev(d1, "XLA Ops", "d", 900, 300)]
    got = scope_trace.chip_busy(events, 0, 1000)
    assert got["planes"] == {D: 80.0, d1: 30.0}  # d's end is cut at the window
    assert got["spread"] == 50.0


def test_merge_seconds_count_the_merge_scope_once_per_plane():
    d1 = "/device:TPU:1"
    merge = M + "lanes.merge/scatter"
    events = [ev(D, "XLA Ops", "a", 0, 400, merge),
              ev(D, "XLA Ops", "b", 100, 100, merge),  # nested: counts once
              ev(D, "XLA Ops", "c", 500, 300, M + "shard_map/track.merge"),
              ev(d1, "XLA Ops", "d", 0, 200, merge),
              ev(d1, "XLA Ops", "e", 300, 100, M + "not.lanes.merge")]
    assert scope_trace.named_seconds(events, 0, 1000, "lanes.merge") == \
        pytest.approx((400 + 200) / 2 / 1e9)


def test_lane_numbers_from_counters_and_spans():
    red = {"dispatches": 10,
           "counters_untraced": {"dispatches": 20, "packets": 20 * 1024,
                                 "padded": 20 * 3 * 1024, "lane_rows_max": 20 * 300},
           "spans_untraced": {"octopus.partition": [20, 0.030]}}
    got = scope_trace.lane_numbers(red, 4, 0.004)
    assert got["lane_fill"] == 25.0
    assert got["lane_skew"] == pytest.approx(4 * 300 / 1024)
    assert got["merge_ms_per_dispatch"] == pytest.approx(0.4)
    assert got["partition_ms_per_dispatch"] == pytest.approx(1.5)
    assert got["scatter_ms_per_dispatch"] == 0.0  # the served path never scatters
    # a program without the counter reads no skew
    red["counters_untraced"]["lane_rows_max"] = 0
    assert scope_trace.lane_numbers(red, 4, 0.0)["lane_skew"] is None
