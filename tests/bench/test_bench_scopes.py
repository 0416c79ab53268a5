"""The reductions of ``bench/scopes.py`` (device time per named scope, idle
gaps named by the program's own spans), on hand-made events and on a short
trace of the served ``ids-cnn.churn.sat`` step recorded on a TPU v5e
(``data/trace_ids-cnn.churn.sat.scoped.json.gz``, kept by
``tools/scope_trace.py --keep``: three dispatches, with the program's
``octopus.*`` spans and each operation's scope and own scope; operations
nested inside another of their scopes pruned, names cut to
``trace.op_label``)."""
import gzip
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import scopes, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SCOPED = DATA / "trace_ids-cnn.churn.sat.scoped.json.gz"
BENCH_ONLY = DATA / "trace_etc-tf.mice.sat.json.gz"
D, D1, H = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, start, dur, scope=None):
    e = {"plane": plane, "line": line, "name": name, "start_ns": start,
         "dur_ns": dur}
    if scope is not None:
        e["scope"] = scope
    return e


def test_top_scope_is_the_outermost_program_scope():
    assert scopes.top_scope("jit(_masked_step)/track.merge/cond/branch_1_fun/"
                            "track.fallback/while") == "track.merge"
    assert scopes.top_scope("jit(_masked_step)/vmap(lane)/drain/top_k") == "drain"
    assert scopes.top_scope("jit(_masked_step)/add") == scopes.UNSCOPED
    assert scopes.top_scope("") == scopes.UNSCOPED


def test_scope_seconds_unions_nested_ops_and_averages_planes():
    M = "jit(_masked_step)/"
    events = [
        ev(H, "python3", "bench.traced", 0, 1000),
        ev(D, "XLA Ops", "while.1", 100, 300, M + "track.promote/while"),
        ev(D, "XLA Ops", "fusion.2", 150, 50, M + "track.promote/while/body/x"),
        ev(D, "XLA Ops", "fusion.3", 380, 40, M + "track.promote/y"),  # overlaps
        ev(D, "XLA Ops", "cond.4", 500, 200, M + "track.merge/cond"),
        ev(D, "XLA Ops", "fusion.5", 550, 50,
           M + "track.merge/cond/branch_1_fun/track.fallback/while"),
        ev(D, "XLA Ops", "copy.6", 950, 100, M + "copy"),  # clipped at 1000
        ev(D1, "XLA Ops", "while.1", 100, 300, M + "track.promote/while"),
        ev(D, "XLA Modules", "jit__masked_step(1)", 100, 900),
    ]
    got = scopes.scope_seconds(events, *trace.window(events))
    # promote: [100,420) on TPU:0, [100,400) on TPU:1, averaged
    assert got == {"track.promote": pytest.approx(310e-9),
                   "track.merge": pytest.approx(100e-9),
                   scopes.UNSCOPED: pytest.approx(25e-9)}
    assert list(got) == ["track.promote", "track.merge", scopes.UNSCOPED]
    ops = scopes.top_ops(events, 0, 1000, n=2)
    assert ops == [["while.1", "track.promote", pytest.approx(300e-9)],
                   ["cond.4", "track.merge", pytest.approx(100e-9)]]


def test_idle_gaps_split_by_the_innermost_program_span():
    events = [
        ev(H, "python3", "bench.traced", 0, 1000),
        ev(H, "python3", "bench.step", 0, 800),
        ev(H, "python3", "octopus.step", 10, 780),
        ev(H, "python3", "octopus.enqueue", 10, 40),
        ev(H, "python3", "octopus.wait", 50, 400),
        ev(H, "python3", "octopus.readback", 450, 100),
        ev(H, "python3", "octopus.feedback", 550, 200),
        ev(H, "executor", "octopus.pack", 820, 100),
        ev(D, "XLA Modules", "jit__masked_step(1)", 100, 300),
        ev(D, "XLA Ops", "while.1", 100, 300, "jit(_masked_step)/drain"),
        ev(D, "XLA Ops", "fusion.2", 410, 30, "jit(_masked_step)/drain"),
    ]
    t0, t1 = trace.window(events)
    gaps = dict(scopes.idle_gaps(events, t0, t1))
    # [0,100): bench.step 0-10, enqueue 10-50, wait 50-100; [400,410) and
    # [440,1000): the wait to 450, readback, feedback, the step's tail,
    # the bench span's tail, nothing, the executor's pack, nothing
    assert gaps == {"bench.step x2": pytest.approx(20e-9),
                    "octopus.enqueue x1": pytest.approx(40e-9),
                    "octopus.wait x3": pytest.approx(70e-9),
                    "octopus.readback x1": pytest.approx(100e-9),
                    "octopus.feedback x1": pytest.approx(200e-9),
                    "octopus.step x1": pytest.approx(40e-9),
                    "octopus.pack x1": pytest.approx(100e-9),
                    "no bench span x1": pytest.approx(100e-9)}
    assert sum(gaps.values()) == pytest.approx(
        (t1 - t0) / 1e9 - trace.busy_seconds(events, t0, t1))
    # the benchmark's reduction names each whole gap by its middle
    assert dict(trace.idle_gaps(events, t0, t1)) == {
        "bench.step x3": pytest.approx(670e-9)}


def test_idle_gaps_of_a_trace_without_program_spans():
    events = json.load(gzip.open(BENCH_ONLY, "rt"))
    t0, t1 = trace.window(events)
    gaps = dict(scopes.idle_gaps(events, t0, t1))
    assert sum(gaps.values()) == pytest.approx(
        (t1 - t0) / 1e9 - trace.busy_seconds(events, t0, t1), rel=1e-6)
    assert all(k.startswith(("bench.", "inside a device program", "no bench span"))
               for k in gaps)
    # a part of a gap that no span covers is no longer the bench span's
    step = lambda g: sum(v for k, v in g.items() if k.startswith("bench.step"))
    assert 0 < step(gaps) <= step(dict(trace.idle_gaps(events, t0, t1)))


HLO = """\
ENTRY %main {
  %p = s32[8]{0} parameter(0), metadata={op_name="state.cold.payload"}
  %copy.1 = s32[8]{0} copy(%p), metadata={op_name="state.cold.payload"}
  %fusion.2 = s32[8]{0} fusion(%copy.1), kind=kLoop, calls=%f, metadata={op_name="jit(_masked_step)/track.promote/scatter" stack_frame_id=3}
  %copy.3 = s32[8]{0} copy(%fusion.2)
  %tuple.4 = (s32[8]{0}) tuple(%copy.3)
  ROOT %while.5 = (s32[8]{0}) while(%tuple.4), condition=%c, body=%b, metadata={op_name="jit(_masked_step)/track.spill/while"}
  %add.6 = s32[] add(%x, %y), metadata={op_name="jit(_masked_step)/add"}
}
"""


def test_op_names_from_compiled_text_and_labels():
    names = scopes.op_names(HLO)
    own = {k: v[0] for k, v in names.items()}
    scoped = {k: v[1] for k, v in names.items()}
    assert own["fusion.2"] == scoped["fusion.2"]
    assert scoped["fusion.2"].endswith("track.promote/scatter")
    # XLA's copies take the scope of the work that consumes them, and keep
    # their own op_name beside it
    assert own["copy.1"] == "state.cold.payload" and own["copy.3"] == ""
    assert scopes.top_scope(scoped["copy.1"]) == "track.promote"
    assert scopes.top_scope(scoped["copy.3"]) == "track.spill"
    assert scopes.top_scope(scoped["add.6"]) == scopes.UNSCOPED
    # the same instruction names another scope in the other bucket's program
    other = {k: ("", "jit(_masked_step)/drain/x") for k in names}
    step = dict(ev(H, "python3", "octopus.step", 0, 40), args={"bucket": 8})
    events = scopes.label([
        step,
        ev(D, "XLA Modules", "jit__masked_step(7)", 0, 15),
        ev(D, "XLA Ops", "%copy.3 = s32[8]{0} copy(s32[8]{0} %fusion.2)", 0, 5),
        ev(D, "XLA Ops", "%while.5 while", 5, 5),
        ev(D, "XLA Ops", "%other.9 add", 10, 5),
        ev(D, "XLA Modules", "jit__masked_step(9)", 50, 10),  # no step span
        ev(D, "XLA Ops", "%while.5 while", 50, 5)], {8: names, 16: other})
    assert [scopes.top_scope(e["scope"]) for e in events if "scope" in e] == [
        "track.spill", "track.spill", scopes.UNSCOPED, "drain"]
    assert [scopes.top_scope(e["own_scope"]) for e in events if "scope" in e] == [
        scopes.UNSCOPED, "track.spill", scopes.UNSCOPED, scopes.UNSCOPED]
    assert "scope" not in step
    # counted by its own op_name, the copy is unscoped; it is the one
    # operation that took its user's scope
    t0, t1 = 0, 60
    assert scopes.scope_seconds(events, t0, t1) == {
        "track.spill": pytest.approx(10e-9), "drain": pytest.approx(5e-9),
        scopes.UNSCOPED: pytest.approx(5e-9)}
    assert scopes.scope_seconds(events, t0, t1, "own_scope") == {
        scopes.UNSCOPED: pytest.approx(15e-9),
        "track.spill": pytest.approx(5e-9)}
    assert scopes.inherited_ops(events, t0, t1) == [
        ["%copy.3 copy", "track.spill", pytest.approx(5e-9)],
        ["%while.5 while", "drain", pytest.approx(5e-9)]]


def test_reduction_of_a_recorded_scoped_chip_trace():
    events = json.load(gzip.open(SCOPED, "rt"))
    red = scopes.reduce(events, "_masked_step")
    t0, t1 = trace.window(events)
    idle = (t1 - t0) / 1e9 - trace.busy_seconds(events, t0, t1)
    assert red["dispatches"] == 3
    # the program's scopes cover the step program's device time
    scoped = {k: v for k, v in red["device_scopes"].items() if k != scopes.UNSCOPED}
    assert sum(scoped.values()) >= 0.9 * red["step_device_s"]
    assert set(scoped) == set(scopes.SCOPES)
    assert {scope for _, scope, _ in red["device_ops"][:4]} <= set(scopes.SCOPES)
    # by each op's own op_name alone they cover less: the two relayout
    # copies of the cold payload name no scope and take promote's and spill's
    own = {k: v for k, v in red["device_scopes_own"].items() if k != scopes.UNSCOPED}
    assert 0.7 * red["step_device_s"] <= sum(own.values()) < sum(scoped.values())
    assert [op[:2] for op in red["inherited_ops"][:2]] == [
        ["%copy.201 copy", "track.promote"], ["%copy.205 copy", "track.spill"]]
    gaps = dict(scopes.idle_gaps(events, t0, t1, n=100))
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # the idle time falls in the program's own spans, not the bench wrapper
    named = sum(v for k, v in gaps.items() if k.startswith("octopus."))
    assert named >= 0.9 * idle
    assert sum(v for k, v in gaps.items() if k.startswith("bench.step")) < 0.1 * idle
    steps = [e for e in events if e["name"] == "octopus.step"]
    assert len(steps) >= 3
    assert all(set(e["args"]) == {"dispatch", "bucket"} for e in steps)
