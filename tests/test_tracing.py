"""Spans and counters inside the served step: the ``repro.runtime.span``
helper, the host spans of the pipelines and the service (and the intervals
``host_s``/``device_s`` sum), the ``jax.named_scope`` names on the step's
device work, and the ``fallback_slots``/``ready_left`` counters."""
import asyncio
import contextlib
import glob
import itertools
import re
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from asyncio_compat import async_test

from repro.core import feature_extractor as fx
from repro.core import flow_tracker as ft
from repro.data.traffic import TrafficConfig, TrafficGenerator
from repro.models import paper_models
from repro.runtime import SpanTotal, name_scope, span
from repro.runtime import trace as rt_trace
from repro.serving import (OctopusPipeline, OctopusService, PipelineConfig,
                           ServiceConfig, ShardedOctopusPipeline)

SCOPES_HOT = ("track.merge", "track.fallback", "drain", "engine.pkt",
              "engine.flow")
SCOPES_COLD = SCOPES_HOT + ("track.promote", "track.spill", "track.scrub")
STEP_SPANS = ("octopus.step", "octopus.enqueue", "octopus.wait",
              "octopus.readback", "octopus.feedback", "octopus.counters")


@pytest.fixture(scope="module")
def mlp_params():
    return paper_models.init_paper_model("mlp", jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def cnn_params():
    return paper_models.init_paper_model("cnn", jax.random.PRNGKey(1))


def make_pipeline(mlp, cnn, *, batch_size=16, table_size=64, max_ready=4,
                  num_shards=0, lane_batch=None, **kw):
    cfg = PipelineConfig(batch_size=batch_size, max_ready=max_ready,
                         flow_model="cnn", table_size=table_size, **kw)
    if num_shards:
        return ShardedOctopusPipeline(mlp, cnn, cfg, num_shards=num_shards,
                                      lane_batch=lane_batch)
    return OctopusPipeline(mlp, cnn, cfg)


def traffic(batch_size, seed, table_size=64, active_flows=24):
    return TrafficGenerator(TrafficConfig(
        batch_size=batch_size, active_flows=active_flows,
        elephant_fraction=0.4, table_size=table_size, seed=seed,
        collision_free=False))


class Stats:
    def __init__(self):
        self.spans = {}


@pytest.fixture
def fake_clock(monkeypatch):
    """``perf_counter`` of the span helper returns 2**i on its i-th call, so
    every span has its own duration and a sum of spans names its terms."""
    calls = itertools.count()
    monkeypatch.setattr(rt_trace, "time", types.SimpleNamespace(
        perf_counter=lambda: float(2 ** next(calls))))


# ------------------------------------------------------------- the helper

def test_span_totals_counts_and_max(fake_clock):
    st = Stats()
    with span("a", st) as s1:
        pass
    with span("b", st, dispatch=7) as s2:
        pass
    with span("a", st) as s3:
        pass
    assert (s1.s, s2.s, s3.s) == (1.0, 4.0, 16.0)
    assert st.spans == {"a": SpanTotal(2, 17.0, 16.0),
                        "b": SpanTotal(1, 4.0, 4.0)}


def test_span_nesting_and_exceptions(fake_clock):
    st = Stats()
    with pytest.raises(ValueError):
        with span("outer", st) as outer:
            with span("inner", st) as inner:
                pass
            raise ValueError("recorded all the same")
    # outer spans calls 0..3, inner calls 1..2
    assert (inner.s, outer.s) == (2.0, 7.0)
    assert st.spans["outer"].count == st.spans["inner"].count == 1


def test_spans_land_in_a_cpu_profiler_trace(tmp_path, mlp_params,
                                            cnn_params):
    from jax.profiler import ProfileData

    pipe = make_pipeline(mlp_params, cnn_params)
    pipe.warm_bucket(16)
    gen = traffic(16, seed=3)
    keep = np.ones(16, bool)
    pipe.step_masked(gen.next_batch(), keep)
    jax.profiler.start_trace(str(tmp_path))
    pipe.step_masked(gen.next_batch(), keep)
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("octopus."):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(STEP_SPANS) <= set(found)
    assert found["octopus.step"] == [{"dispatch": 1, "bucket": 16}]


# ------------------------------------------------ the pipelines' host spans

def test_step_masked_host_s_sums_enqueue_readback_feedback(
        fake_clock, mlp_params, cnn_params):
    pipe = make_pipeline(mlp_params, cnn_params)
    pipe.warm_bucket(16)
    gen = traffic(16, seed=4)
    keep = np.arange(16) < 11
    for _ in range(3):
        pipe.step_masked(gen.next_batch(), keep)
    s, sp = pipe.stats, pipe.stats.spans
    assert all(sp[k].count == 3 for k in STEP_SPANS)
    tot = {k: v.total_s for k, v in sp.items()}
    # the intervals host_s summed before spans: enqueue, then read-back and
    # feedback; the wait is device_s; the counters' read is in neither
    assert s.host_s == (tot["octopus.enqueue"] + tot["octopus.readback"]
                        + tot["octopus.feedback"])
    assert s.device_s == tot["octopus.wait"]
    assert s.total_s == s.host_s + s.device_s
    assert s.packets == 33 and s.padded == 15 and s.dispatches == 3


@pytest.mark.parametrize("overlap", [False, True])
def test_run_host_s_adds_the_pull(fake_clock, mlp_params, cnn_params,
                                  overlap):
    pipe = make_pipeline(mlp_params, cnn_params, scan_len=2, overlap=overlap)
    pipe.warmup()
    pipe.run(traffic(16, seed=5), steps=5)  # two chunks and one single step
    s, tot = pipe.stats, {k: v.total_s for k, v in pipe.stats.spans.items()}
    assert pipe.stats.spans["octopus.pull"].count == 3
    assert s.dispatches == 3 and s.steps == 5
    assert s.host_s == sum(tot[k] for k in ("octopus.pull", "octopus.enqueue",
                                            "octopus.readback",
                                            "octopus.feedback"))
    assert s.device_s == tot["octopus.wait"]


def test_sharded_spans_partition_scatter_and_rounds(fake_clock, mlp_params,
                                                    cnn_params):
    """Overflow rounds: partition and the verdict scatter are host spans,
    counted in host_s, and the counters of every round are summed."""
    pipe = make_pipeline(mlp_params, cnn_params, batch_size=16, max_ready=4,
                         num_shards=2, lane_batch=4)
    pipe.warmup()
    gen = traffic(16, seed=6)
    s, sp = pipe.stats, pipe.stats.spans
    for _ in range(3):
        before = (s.new_flows, s.evicted, s.fallback_slots, s.ready_left)
        out = pipe.step(gen.next_batch())
        # the step's output carries the counters summed over its rounds
        assert tuple(int(x) for x in (out.new_flows, out.evicted,
                                      out.fallback_slots, out.ready_left)) \
            == tuple(a - b for a, b in zip((s.new_flows, s.evicted,
                                            s.fallback_slots, s.ready_left),
                                           before))
    tot = {k: v.total_s for k, v in sp.items()}
    assert sp["octopus.partition"].count == 3
    assert sp["octopus.scatter"].count == 3  # every step overflowed a lane
    assert s.dispatches > 3
    assert s.host_s == sum(tot[k] for k in ("octopus.partition",
                                            "octopus.enqueue",
                                            "octopus.readback",
                                            "octopus.feedback"))
    # the scatter is part of the read-back, not beside it
    assert tot["octopus.scatter"] < tot["octopus.readback"]
    assert s.new_flows > 0


@pytest.mark.parametrize("num_shards", [0, 2])
def test_one_readback_span_per_dispatch(mlp_params, cnn_params, num_shards):
    """The served step reads its host pack once: one ``octopus.readback``
    per dispatch, counted in ``host_reads``, and the counters' slicing
    still its own ``octopus.counters`` span once per dispatch."""
    pipe = make_pipeline(mlp_params, cnn_params, num_shards=num_shards)
    pipe.warm_bucket(16)
    gen = traffic(16, seed=8)
    for n in (16, 9, 12, 16):
        pipe.step_masked(gen.next_batch(), np.arange(16) < n)
    s, sp = pipe.stats, pipe.stats.spans
    assert s.dispatches == 4
    assert sp["octopus.readback"].count == s.dispatches == s.host_reads
    assert sp["octopus.counters"].count == s.dispatches


# -------------------------------------------------- the service's host spans

@async_test
async def test_service_spans_device_s_and_dispatch_numbers(
        mlp_params, cnn_params):
    pipe = make_pipeline(mlp_params, cnn_params)
    svc = OctopusService(pipe, ServiceConfig(buckets=(8, 16)))
    threads = set()
    step = pipe.step_masked

    def spy(packets, keep):
        threads.add(threading.current_thread().name)
        return step(packets, keep)

    pipe.step_masked = spy
    async with svc:
        gens = [traffic(5, seed=10 + i) for i in range(3)]
        first = await asyncio.gather(*(svc.submit(g.next_batch(), client_id=i)
                                       for i, g in enumerate(gens)))
        big = await svc.submit(traffic(40, seed=20).next_batch())
    # the pack, copy and step ran on the offload thread
    assert threads and all(t.startswith("octopus-dispatch") for t in threads)
    s, p = svc.stats, pipe.stats
    n = s.dispatches
    assert n == p.dispatches
    for k in ("octopus.dispatch", "octopus.pack", "octopus.h2d",
              "octopus.d2h", "octopus.answer"):
        assert s.spans[k].count == n
    # the batcher's span before each dispatch, and the one that saw the stop
    assert s.spans["octopus.batch"].count == n + 1
    assert p.spans["octopus.step"].count == n
    # host_s: the pack, the copy in and the verdicts' copy out, as before
    # spans; the answer on the loop thread is apart
    assert s.host_s == pytest.approx(s.spans["octopus.pack"].total_s
                                     + s.spans["octopus.h2d"].total_s
                                     + s.spans["octopus.d2h"].total_s)
    # device_s is the time blocked on the device: the pipeline's wait
    assert s.device_s == pytest.approx(p.spans["octopus.wait"].total_s)
    assert s.device_s == pytest.approx(p.device_s)
    numbers = [d for r in first + [big] for d in r.dispatches]
    assert sorted(set(numbers)) == list(range(n))
    assert len(big.dispatches) == len(big.buckets) == 3  # 40 rows: 16+16+8
    assert big.dispatches == tuple(sorted(big.dispatches))


# ------------------------------------------------------- device scopes

def _compiled(pipe, bucket=16):
    return pipe._masked_fn.lower(pipe.state, pipe._zero_batch(bucket),
                                 jnp.ones((bucket,), bool)).compile().as_text()


@pytest.mark.parametrize("cold_size", [0, 128])
def test_named_scopes_in_the_compiled_masked_step(mlp_params, cnn_params,
                                                  monkeypatch, cold_size):
    pipe = make_pipeline(mlp_params, cnn_params, cold_size=cold_size)
    text = _compiled(pipe)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    want = SCOPES_COLD if cold_size else SCOPES_HOT
    for scope in want:
        assert any(f"/{scope}/" in n for n in names), scope
    if not cold_size:
        assert not any("/track.promote/" in n for n in names)
    # the scopes are metadata only: without them the ops are the same
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled(make_pipeline(mlp_params, cnn_params,
                                   cold_size=cold_size))

    def ops(t):
        return [re.sub(r", metadata=\{[^}]*\}", "", ln)
                for ln in t.splitlines() if " = " in ln]

    assert ops(text) == ops(bare)


def test_route_name_scope_is_a_jax_name_scope():
    def f(x):
        with name_scope("engine.pkt"):
            return jnp.sin(x) * 2

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "engine.pkt/sin" in text


# ------------------------------------------------------------- counters

def _slots_with_mixed_hashes(packets, keep, table_size):
    h = np.asarray(packets.tuple_hash)[keep]
    slots = np.asarray(ft.hash_slot(jnp.asarray(h), table_size))
    per_slot = {}
    for s, x in zip(slots, h):
        per_slot.setdefault(int(s), set()).add(int(x))
    return sum(len(v) > 1 for v in per_slot.values())


def test_fallback_slots_and_ready_left_on_constructed_batches(mlp_params,
                                                              cnn_params):
    # few flows on a small table: slots collide inside batches, and flows
    # reach top_n together, more than the drain budget of one
    T, B, R = 16, 32, 1
    pipe = make_pipeline(mlp_params, cnn_params, batch_size=B, table_size=T,
                         max_ready=R)
    pipe.warm_bucket(B)
    gen = traffic(B, seed=8, table_size=T, active_flows=6)
    seen_fb = seen_left = 0
    for i in range(12):
        batch = gen.next_batch()
        keep = np.arange(B) < (B - i)
        collide = bool(fx.batch_collisions(batch, T, jnp.asarray(keep)))
        want_fb = _slots_with_mixed_hashes(batch, keep, T)
        out = pipe.step_masked(batch, keep)
        assert int(out.fallback_slots) == want_fb
        assert collide == (want_fb > 0)
        # what is left ready after the drain is what the state still holds
        ready_after = int(ft.ready_mask(pipe.state, top_n=pipe.cfg.top_n).sum())
        assert int(out.ready_left) == ready_after
        drained = int(np.asarray(out.drained.mask).sum())
        assert drained == R or int(out.ready_left) == 0
        seen_fb += want_fb > 0
        seen_left += int(out.ready_left) > 0
    s = pipe.stats
    assert s.fallback_dispatches == seen_fb > 0
    assert s.ready_left > 0 and seen_left > 0
    assert s.fallback_slots >= s.fallback_dispatches
