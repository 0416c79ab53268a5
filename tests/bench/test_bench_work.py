"""The counts behind ``step_roofline`` and ``step_mfu``, the peaks table, and
the trace reductions, on hand-made events and on a short trace of the
served step recorded on a TPU v5e (``data/trace_etc-tf.mice.sat.json.gz``:
three dispatches of the etc-tf step program with the harness's host
spans)."""
import gzip
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import models, trace, work  # noqa: E402

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"
RECORDED = Path(__file__).resolve().parent / "data" / "trace_etc-tf.mice.sat.json.gz"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_model_flops_by_hand():
    assert work.mlp_flops([6, 12, 6, 3, 2]) == 2 * (72 + 72 + 18 + 6)
    cnn = cfg("ids-cnn")["flow_model"]
    # conv 20x(3*1)x32, 10x(3*32)x32, 5x(3*32)x32, then 96x128 and 128x162
    assert work.flow_flops(cnn) == 2 * (20 * 3 * 32 + 10 * 96 * 32 + 5 * 96 * 32
                                        + 96 * 128 + 128 * 162)
    tf = cfg("etc-tf")["flow_model"]
    # q, k, v 15x16x64 each; scores and mix 15x15x64 each; MLP 15x64x128 twice
    assert work.flow_flops(tf) == 2 * (3 * 15 * 16 * 64 + 2 * 15 * 15 * 64
                                       + 2 * 15 * 64 * 128 + 64 * 162)


def test_bytes_count_records_not_the_table():
    c = cfg("ids-cnn")
    rec = work.record_bytes(c)
    assert rec == 4 * (3 + 16 + 20 + 20 + 15 * 16)  # about 1.2 KB a slot
    wb = work.weight_bytes(c)
    assert wb == 4 * sum(int(__import__("math").prod(s)) for g in models.shapes(c).values()
                         for s in g.values())
    f, b = work.dispatch_work(c, packets=256, slots=100, flows=10, cold_moves=30)
    assert f == 256 * 336 + 10 * work.flow_flops(c["flow_model"])
    assert b == 256 * (4 * 22 + 4) + 2 * 100 * rec + 2 * 30 * (rec + 4) + wb
    # nothing scales with the table or the cold tier's size
    big = dict(c, table_size=1 << 20, cold_size=1 << 22)
    assert work.dispatch_work(big, packets=256, slots=100, flows=10, cold_moves=30) == (f, b)


def test_roofline_names_its_bound():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.roofline_seconds(1e6, 1e6, peak)
    assert bound == "memory" and t == pytest.approx(1e6 / 819e9)
    t, bound = work.roofline_seconds(1e12, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)


def test_peaks_table_and_unknown_devices():
    p = work.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        197e12, 393e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        work.peaks("cpu")


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start, "dur_ns": dur}


def test_busy_union_gaps_and_programs_by_hand():
    D = "/device:TPU:0"
    events = [
        ev("/host:CPU", "python3", "bench.traced", 0, 1000),
        ev(D, "XLA Modules", "jit__masked_step(1)", 100, 300),
        ev(D, "XLA Ops", "while.1", 100, 200),
        ev(D, "XLA Ops", "fusion.2", 150, 100),  # nested in the while: counted once
        ev(D, "XLA Ops", "copy.3", 350, 50),  # a gap of 50 inside the program
        ev(D, "XLA Ops", "copy.4", 900, 200),  # clipped at the window's end
        ev("/host:CPU", "python3", "bench.step", 50, 400),
        ev("/host:CPU", "python3", "bench.make_traffic", 500, 100),
    ]
    t0, t1 = trace.window(events)
    assert (t0, t1) == (0, 1000)
    assert trace.busy_intervals(events, D, t0, t1) == [(100, 300), (350, 400), (900, 1000)]
    assert trace.busy_seconds(events, t0, t1) == pytest.approx(350e-9)
    assert trace.program_seconds(events, "_masked_step", t0, t1) == pytest.approx(300e-9)
    gaps = dict(trace.idle_gaps(events, t0, t1))
    # [0,100) in bench.step; [300,350) inside the program; [400,900) mid 650:
    # no span covers it
    assert gaps == {"bench.step x1": pytest.approx(100e-9),
                    "inside a device program x1": pytest.approx(50e-9),
                    "no bench span x1": pytest.approx(500e-9)}
    ops = dict(trace.top_ops(events, t0, t1))
    assert ops["while.1"] == pytest.approx(200e-9) and "copy.4" in ops


def test_reduction_of_a_recorded_chip_trace():
    events = json.load(gzip.open(RECORDED, "rt"))
    red = trace.reduce(events, "_masked_step")
    assert red["device_planes"] == 1
    assert red["window_s"] == pytest.approx(0.029282366)
    # three step programs of about 0.65 ms each, nearly all of it busy
    assert red["step_device_s"] == pytest.approx(3 * 0.000646, rel=0.01)
    assert red["step_device_s"] * 0.99 < red["busy_s"] <= red["window_s"]
    gaps = dict(red["idle_gaps"])
    # the host's step call (staging, feedback) leaves the device idle most of the window
    step_gap = next(v for k, v in gaps.items() if k.startswith("bench.step"))
    assert step_gap > 0.5 * (red["window_s"] - red["busy_s"])
    assert sum(gaps.values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert len(red["device_ops"]) == 10 and all(s > 0 for _, s in red["device_ops"])
