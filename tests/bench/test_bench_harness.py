"""The harness end to end at a size a test run can hold, on the CPU: cells,
configurations, mixes and metrics are found by name (a metric added as a
file is read without a code change), a sound run is ``correct``, and a run
whose timed path is broken underneath is not, once for each fault the
cells can have.  The reference in bfloat16 put in the program's place (the
control), at float8 operands, and with one answer altered per request,
fail the configuration's limits too."""
import copy
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def tiny(name: str, config: str = "") -> harness.Cell:
    """The cell at a few hundred flows: same models, mix and limits; with
    ``config``, that configuration file in place of the cell's."""
    cell = harness.load_cell(name)
    if config:
        cell.config = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    c = copy.deepcopy(cell.config)
    lanes = c["lanes"]
    c.update(table_size=64, cold_size=512 if c["cold_size"] else 0, live_flows=256,
             max_ready=4 * lanes, buckets=[32 * lanes], depth_budget=4096)
    m = copy.deepcopy(cell.mix)
    m.update(ports_per_lane=2, request_packets=8)
    if m["loop"] == "open":
        m["rate_pkt_per_s_per_lane"] = 400
    cell.config, cell.mix = c, m
    return cell


def run(cell, patch=None, control=(), seconds=0.6):
    return harness.run_cell(cell, 2**31 + 7, seconds, False, t_start=time.perf_counter(),
                            require_chip=False, patch=patch, control=control)


def test_cells_are_found_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"] and cell.config["name"] == w["config"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_reader(m["name"]))


def test_a_metric_file_is_all_a_new_metric_needs(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "dispatches_per_s", "unit": "1/s", "better": "higher",
                               "source": "program_counter", "layer": "service front end",
                               "moves": "pkt_per_s.hostbound", "workloads": ["etc-tf.mice.sat"]})
    bench["per_layer"].append({"name": "silent", "unit": "%", "better": "higher",
                               "source": "device_trace", "layer": "device",
                               "moves": "pkt_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(ROOT / "bench" / "configs", tmp_path / "bench" / "configs")
    metrics = tmp_path / "metrics"
    shutil.copytree(ROOT / "bench" / "metrics", metrics)
    (metrics / "dispatches_per_s.py").write_text(
        "def read(run):\n    return run['service']['dispatches'] / run['seconds']\n")
    (metrics / "silent.py").write_text("def read(run):\n    return None\n")
    cell = harness.load_cell("etc-tf.mice.sat", tmp_path)
    assert [m["name"] for m in cell.per_layer][-2:] == ["dispatches_per_s", "silent"]
    assert "dispatches_per_s" not in {m["name"] for m in harness.load_cell(
        "ids-cnn.churn.sat", tmp_path).per_layer}
    got = harness.read_metrics(cell.per_layer[-2:], {"service": {"dispatches": 50},
                                                     "seconds": 10.0}, metrics)
    assert got == {"dispatches_per_s": {"value": 5.0, "unit": "1/s"}}  # silent left out


@pytest.fixture(scope="module")
def churn():
    return tiny("ids-cnn.churn.sat")


def test_sound_run_is_correct_and_the_control_is_not(churn):
    r = run(churn, control=("bf16", "fp8", "altered"))
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"pkt_per_s", "flow_per_s", "setup_s"}
    assert r["device"]["platform"] == "cpu"
    assert [k for k in r if not k.startswith("_")][-1] == "compared"  # last in the line
    lim = churn.config["limits"]
    for control in ("bf16", "fp8", "altered"):
        over = [k for k, v in r["control"][control].items() if v > lim[k]]
        assert over, r["control"]
    # a single altered answer per request or dispatch fails every widest number
    assert all(r["control"]["altered"][k] > lim[k] for k in ("pkt_gap", "flow_gap", "score_err"))


def _masked(fn):
    """Replace the pipeline's jitted bucket step by ``fn(orig)``."""
    def patch(pipe, svc):
        orig = pipe._masked_step
        pipe._masked_fn = jax.jit(fn(orig))
    return patch


def _unchanged(orig):
    return lambda s, p, k: (s, orig(s, p, k)[1])


def _half(orig):
    return lambda s, p, k: orig(s, p, k & (jnp.arange(k.shape[0]) < k.sum() // 2))


def _altered(orig):
    def step(s, p, k):
        s, out = orig(s, p, k)
        return s, out._replace(pkt_actions=out.pkt_actions.at[0].set(1 - out.pkt_actions[0]),
                               flow_cls=(out.flow_cls + 1) % 162)
    return step


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state-unchanged", "half-batch", "answer-altered"])
def test_broken_timed_path_is_not_correct(churn, fault):
    r = run(churn, patch=_masked(fault))
    assert not r["correct"], r["compared"]


def test_lanes_without_their_exchange_are_not_correct():
    cell = tiny("ids-cnn.churn.sat", "ids-cnn-x4")

    def patch(pipe, svc):
        merge = pipe._merge_out

        def lane0_only(outs, src, *, batch=None):
            # what the other lanes computed (verdicts, drained flows) never
            # reaches the merged answer
            lanes = jnp.arange(src.shape[0])[:, None]
            d = outs.drained._replace(mask=outs.drained.mask & (lanes == 0))
            return merge(outs._replace(drained=d),
                         jnp.where(lanes == 0, src, src.shape[1]), batch=batch)
        pipe._merge_out = lane0_only

    assert run(cell)["correct"]
    assert not run(cell, patch=patch)["correct"]


def test_open_loop_cell_runs_and_reports_its_tail():
    r = run(tiny("ids-cnn.churn.p99"), seconds=1.0)
    assert r["correct"], r["compared"]
    assert {"p50_ms", "setup_s"} == set(r["metrics"])
    assert r["attempted"] == round(400 * 1.0 / 8)


def _bench_run(root: Path, env: dict):
    import os
    import subprocess

    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "etc-tf.mice.sat", "--seed", str(2**31 + 9),
         "--seconds", "1", "--trace", "0"],
        cwd=root, env={**os.environ, **env}, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["cpu-only", "benchmark-files-only"])
def test_refuses_without_a_chip_or_the_system(tmp_path, where):
    """No result line and a non-zero exit without a TPU, and in a directory
    that holds only the benchmark's own files."""
    if where == "cpu-only":
        r = _bench_run(ROOT, {"JAX_PLATFORMS": "cpu"})
        assert "no TPU" in r.stderr
    else:
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        shutil.copytree(ROOT / "bench", tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        r = _bench_run(tmp_path, {"JAX_PLATFORMS": "cpu"})
        assert "system under test" in r.stderr
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_traced_runs_read_host_numbers_from_before_the_profiler():
    reqs = [harness.Request(0, {}, due) for due in (1.0, 5.0, 8.9, 9.2, 9.6)]
    assert harness.host_timed(reqs, None) == reqs
    assert [r.due for r in harness.host_timed(reqs, {"t": 9.5})] == [1.0, 5.0, 8.9]
