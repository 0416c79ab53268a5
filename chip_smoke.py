#!/usr/bin/env python3
"""Smoke run of the Octopus streaming pipeline and service on a TPU.

Drives the system's entry points once at the paper's sizes, with model
parameters and traffic made from ``--seed``, and checks what comes out:

  A  ``OctopusPipeline`` on the default (plain XLA) path: the packet MLP
     (6-12-6-3-2) and the flow 1D-CNN (162 classes) at their published
     widths, the paper's 8192-slot flow table, 256-packet microbatches,
     ``scan_len=8``, traffic whose in-batch slot collisions take the
     segmented tracker's fallback.  Tracker state and drained flows must be
     bit-exact against the pure-Python oracle tracker; packet and flow
     verdicts must agree with the same models' float32 forward on the host
     CPU (see ``P_TOL``); nothing may retrace after ``warmup()``.
  B  the same under ``RuntimeConfig(use_pallas=True)``: the tracker fold and
     both engines run as compiled Pallas kernels, and the compiled step
     must contain ``tpu_custom_call``.
  C  the cold tier: ``cold_size=131072`` behind the hot table and a live
     population larger than it, bit-exact against the two-level oracle.
  D  ``OctopusService`` over the phase-A pipeline: concurrent clients with
     ragged request sizes, bucketed.  No failed dispatch, every request
     answered, no retrace after ``start()``, and the verdicts and tracker
     state equal the pipeline's when it is fed the same dispatches
     synchronously.

``--four-chips`` runs only the sharded phase: ``ShardedOctopusPipeline``
with one tracker bank per chip (``shard_map`` lanes), bit-exact with the
single-lane pipeline on the same traffic.

One process, no child processes.  The script exits non-zero, and prints no
result, when JAX finds no TPU or any check fails.  Otherwise the last line
of standard output is ``{"ok": true, "device": {...}}``.  JAX's compilation
cache is kept in ``$JAX_COMPILATION_CACHE_DIR`` when set, else in
``.jax_cache/`` of the checkout.  Run from the root of a checkout:

    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import traceback
from dataclasses import dataclass, replace
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.abspath(__file__))

# Verdict tolerance against the host float32 forward, in probability: the
# chip's matmuls round f32 operands to bf16 by default, so a verdict may
# differ only where the reference decision is this close to its boundary
# (deny threshold, or the top-2 class margin), and a flow's score may
# differ by at most this much.
P_TOL = 0.05


@dataclass(frozen=True)
class Sizes:
    table_size: int = 8192  # the paper's flow table
    batch_size: int = 256  # packets per microbatch
    max_ready: int = 64  # drained flows per step
    scan_len: int = 8  # microbatches per dispatch
    flows: int = 2000  # live flows (phases A, B, D and the sharded phase)
    steps: int = 96  # microbatches (phases A, B and the sharded phase)
    cold_size: int = 131072  # cold-tier slots (phase C)
    cold_flows: int = 12000  # more live flows than hot slots
    cold_steps: int = 64
    buckets: tuple = (64, 128, 256)  # service batch shapes
    clients: int = 4
    requests: int = 6  # per client


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else "not reported"


def models(seed: int):
    import jax
    from repro.models import paper_models

    return (paper_models.init_paper_model("mlp", jax.random.PRNGKey(seed)),
            paper_models.init_paper_model("cnn", jax.random.PRNGKey(seed + 1)))


def pipeline_config(sizes: Sizes, **kw):
    from repro.serving import PipelineConfig

    return PipelineConfig(batch_size=sizes.batch_size,
                          max_ready=sizes.max_ready, flow_model="cnn",
                          table_size=sizes.table_size,
                          scan_len=sizes.scan_len, **kw)


def make_traffic(sizes: Sizes, seed: int, *, flows: int, steps: int,
                 batch_size: int | None = None, collision_free: bool = False,
                 client_id: int = 0) -> list:
    """Seeded mice/elephant microbatches; elephants burst so that flows
    reach the ready threshold within a few dozen microbatches."""
    from repro.data.traffic import TrafficConfig, TrafficGenerator

    gen = TrafficGenerator(TrafficConfig(
        batch_size=batch_size or sizes.batch_size, active_flows=flows,
        table_size=sizes.table_size, elephant_fraction=0.5, burst_prob=0.5,
        burst_len=8, collision_free=collision_free, seed=seed,
        client_id=client_id))
    return [gen.next_batch() for _ in range(steps)]


class Reference:
    """The paper models' float32 forward on the host CPU device."""

    def __init__(self, mlp, cnn):
        import jax
        from repro.core.feature_extractor import packet_meta_features
        from repro.runtime import RuntimeConfig
        from repro.serving.packet_path import FlowEngine, PacketEngine

        self.cpu = jax.devices("cpu")[0]
        cfg = RuntimeConfig()  # no Pallas: the plain jnp forward
        pkt = PacketEngine(jax.device_put(mlp, self.cpu), config=cfg)
        flow = FlowEngine(jax.device_put(cnn, self.cpu), "cnn", config=cfg)
        self._pkt = jax.jit(lambda b: jax.nn.softmax(
            pkt.fn(pkt.params, packet_meta_features(b)), axis=-1))
        self._flow = jax.jit(lambda s, p: jax.nn.softmax(
            flow.fn(flow.params, flow.prep(s, p)), axis=-1))
        self.max_score_err = 0.0
        self.boundary = 0  # verdicts excused as within P_TOL of a boundary

    def _host(self, tree):
        import jax

        return jax.device_put(tree, self.cpu)

    def check(self, batch, out, keep=None, deny_threshold: float = 0.5):
        """Verdicts of one step output (host numpy) against the reference."""
        import numpy as np

        p = np.asarray(self._pkt(self._host(batch)))[:, 1]
        want = p > deny_threshold
        got = out.pkt_actions.astype(bool)
        off = got != want
        if keep is not None:
            off &= keep
        assert not (off & (np.abs(p - deny_threshold) > P_TOL)).any(), \
            "packet verdicts differ from the host f32 forward"
        self.boundary += int(off.sum())

        d = out.drained
        m = d.mask.astype(bool)
        if not m.any():
            return
        probs = np.asarray(self._flow(self._host(d.series),
                                      self._host(d.payload)))[m]
        top2 = np.sort(probs, axis=-1)[:, -2:]
        err = float(np.abs(out.flow_scores[m] - top2[:, 1]).max())
        self.max_score_err = max(self.max_score_err, err)
        assert err <= P_TOL, f"flow score off by {err} from the host f32 forward"
        off = out.flow_cls[m] != probs.argmax(axis=-1)
        assert not (off & (top2[:, 1] - top2[:, 0] > P_TOL)).any(), \
            "flow classes differ from the host f32 forward"
        self.boundary += int(off.sum())


def check_hot_state(hot, oracle) -> None:
    """Hot tracker bank (host numpy) equals the oracle's table exactly."""
    import numpy as np

    live = np.flatnonzero(hot.count > 0)
    assert set(live.tolist()) == set(oracle.slots), "live slots differ"
    for s in live:
        e = oracle.slots[int(s)]
        assert (hot.tuple_id[s], hot.count[s], hot.last_ts[s]) == (
            e["tuple_id"], e["count"], e["last_ts"]), f"slot {s}"
        for name, want in (("features", oracle.feature_word(e)),
                           ("series", e["series"]), ("sizes", e["sizes"]),
                           ("payload", e["payload"])):
            np.testing.assert_array_equal(getattr(hot, name)[s],
                                          np.asarray(want, np.int32),
                                          err_msg=f"{name} @ slot {s}")


def oracle_run(sizes: Sizes, traffic: list):
    """The pure-Python tracker over ``traffic``: (oracle, per-step expected
    drains, number of microbatches holding an in-batch slot collision)."""
    from repro.models import paper_models
    from test_pipeline import OracleTracker, batch_as_dicts

    oracle = OracleTracker(sizes.table_size, top_n=paper_models.CNN_SEQ,
                           top_k=paper_models.TF_PKTS,
                           pay_bytes=paper_models.TF_BYTES)
    expect, mixed = [], 0
    for batch in traffic:
        pkts = batch_as_dicts(batch)
        hashes: dict[int, set] = {}
        for pkt in pkts:
            hashes.setdefault(oracle.slot_of(pkt["tuple_hash"]), set()).add(
                pkt["tuple_hash"])
            oracle.process(pkt)
        mixed += any(len(h) > 1 for h in hashes.values())
        expect.append(oracle.drain_ready(sizes.max_ready))
    return oracle, expect, mixed


def drive(name: str, pipe, traffic: list, expect: list, oracle, ref, *,
          need_drains: bool = True) -> None:
    """Warm the chunked path, run ``traffic`` in ``scan_len`` chunks, and
    check every step's drains (bit-exact) and verdicts (``Reference``)."""
    import jax
    import numpy as np
    from test_cold_store import assert_drained_equal

    L = pipe.cfg.scan_len
    t0 = time.perf_counter()
    pipe.warmup()
    compile_s = time.perf_counter() - t0
    traces = pipe.trace_count
    t0 = time.perf_counter()
    outs = [pipe.step_many(traffic[k:k + L])
            for k in range(0, len(traffic), L)]
    run_s = time.perf_counter() - t0
    assert pipe.trace_count == traces, "retraced after warmup()"
    for k, out in enumerate(jax.device_get(outs)):
        for j in range(L):
            step = jax.tree_util.tree_map(lambda a: a[j], out)
            i = k * L + j
            assert_drained_equal(step, expect[i], oracle)
            ref.check(traffic[i], step)
    drained = sum(len(e) for e in expect)
    assert drained or not need_drains, "no flow reached the ready threshold"
    s = pipe.stats
    log(name, compile_s=f"{compile_s:.3f}", steps=s.steps,
        dispatches=s.dispatches, trace_count=pipe.trace_count,
        drained=drained, new_flows=s.new_flows, evicted=s.evicted,
        spilled=s.spilled, promoted=s.promoted,
        run_s=f"{run_s:.4f}", device=repr(jax.devices()[0].device_kind),
        max_score_err=f"{ref.max_score_err:.2e}",
        boundary_verdicts=ref.boundary, peak_bytes_in_use=_peak_bytes())


def phase_pipeline(name: str, sizes: Sizes, mlp, cnn, traffic, expected,
                   ref, *, use_pallas: bool):
    """Phases A and B: the hot-only pipeline against the oracle tracker."""
    import jax
    import jax.numpy as jnp
    from repro.runtime import RuntimeConfig
    from repro.serving import OctopusPipeline

    oracle, expect, mixed = expected
    assert mixed > 0, "traffic never exercised the collision fallback"
    pipe = OctopusPipeline(mlp, cnn, pipeline_config(sizes),
                           config=RuntimeConfig(use_pallas=use_pallas))
    if use_pallas and not pipe.runtime.interpret:
        # compiles the chunk program that warmup() then finds in the cache
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                         *traffic[:sizes.scan_len])
        t0 = time.perf_counter()
        hlo = pipe._chunk_fn.lower(pipe.state, stacked).compile().as_text()
        assert "tpu_custom_call" in hlo, "no Pallas kernel in the compiled step"
        log(name, compile_s=f"{time.perf_counter() - t0:.3f}",
            tpu_custom_calls=hlo.count("tpu_custom_call"))
    drive(name, pipe, traffic, expect, oracle, ref)
    check_hot_state(jax.device_get(pipe.state), oracle)
    assert pipe.stats.evicted > 0, "no collision reached the tracker"
    return pipe


def phase_cold(sizes: Sizes, mlp, cnn, seed: int, ref) -> None:
    """Phase C: hot table + cold tier against the two-level oracle."""
    import jax
    from repro.models import paper_models
    from repro.serving import OctopusPipeline
    from test_cold_store import TwoLevelOracle, assert_two_level_state_equal
    from test_pipeline import batch_as_dicts

    traffic = make_traffic(sizes, seed + 2, flows=sizes.cold_flows,
                           steps=sizes.cold_steps)
    oracle = TwoLevelOracle(sizes.table_size, sizes.cold_size,
                            paper_models.CNN_SEQ, paper_models.TF_PKTS,
                            paper_models.TF_BYTES)
    expect = [oracle.step_batch(batch_as_dicts(b), sizes.max_ready)
              for b in traffic]
    pipe = OctopusPipeline(mlp, cnn,
                           pipeline_config(sizes, cold_size=sizes.cold_size))
    # too few packets per flow to reach the ready threshold: this phase is
    # about spills and promotions
    drive("C", pipe, traffic, expect, oracle, ref, need_drains=False)
    assert_two_level_state_equal(jax.device_get(pipe.state), oracle)
    assert (pipe.stats.spilled, pipe.stats.promoted) == (
        oracle.spilled, oracle.promoted)
    assert oracle.spilled > 0 and oracle.promoted > 0, "cold tier idle"


def phase_service(sizes: Sizes, pipe, seed: int) -> None:
    """Phase D: the async service over ``pipe``, then the same dispatches
    replayed through the pipeline synchronously."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.flow_tracker import PacketBatch
    from repro.serving import OctopusService, ServeResult, ServiceConfig

    pipe.reset()
    top = sizes.buckets[-1]
    rng = np.random.default_rng(seed + 3)
    requests = {}
    for c in range(sizes.clients):
        batches = make_traffic(sizes, seed + 10 + c, batch_size=top,
                               flows=max(1, sizes.flows // sizes.clients),
                               steps=sizes.requests, client_id=c)
        requests[c] = [jax.tree_util.tree_map(lambda a: a[:n], b)
                       for b, n in zip(batches, rng.integers(1, top + 1,
                                                              len(batches)))]

    dispatched = []  # (host leaves, keep, verdicts) per service dispatch
    step_masked = pipe.step_masked

    def recording_step(batch, keep):
        out = step_masked(batch, keep)
        # copies: on a CPU backend the batch aliases the pooled buffer
        dispatched.append((jax.tree_util.tree_map(np.array, batch),
                           np.array(keep), np.array(out.pkt_actions)))
        return out

    pipe.step_masked = recording_step

    async def client(svc, c):
        return [await svc.submit(b, client_id=c) for b in requests[c]]

    async def serve():
        svc = OctopusService(pipe, ServiceConfig(
            buckets=tuple(sizes.buckets),
            depth_budget=2 * top * sizes.clients))
        t0 = time.perf_counter()
        await svc.start()
        compile_s = time.perf_counter() - t0
        traces = svc.trace_count
        results = await asyncio.gather(*(client(svc, c) for c in requests))
        await svc.stop()
        return svc, results, traces, compile_s

    try:
        svc, results, traces, compile_s = asyncio.run(serve())
    finally:
        del pipe.step_masked  # back to the class method
    st = svc.stats
    sent = sizes.clients * sizes.requests
    assert st.failed_dispatches == 0, f"{st.failed_dispatches} failed dispatches"
    assert st.served_requests == sent and st.shed_requests == 0, \
        (st.served_requests, st.shed_requests, sent)
    assert svc.trace_count == traces, "retraced after start()"
    assert all(isinstance(r, ServeResult) for rs in results for r in rs)

    served_state = jax.device_get(pipe.state)
    pipe.reset()
    verdict = {}  # (tuple_hash, ts, size) -> sync verdict
    for leaves, keep, actions in dispatched:
        out = pipe.step_masked(
            PacketBatch(*(jnp.asarray(a) for a in leaves)), keep)
        np.testing.assert_array_equal(np.asarray(out.pkt_actions)[keep],
                                      actions[keep])
        for h, t, z, a in zip(leaves.tuple_hash[keep], leaves.ts[keep],
                              leaves.size[keep], actions[keep]):
            assert verdict.setdefault((h, t, z), a) == a
    for x, y in zip(jax.tree_util.tree_leaves(served_state),
                    jax.tree_util.tree_leaves(jax.device_get(pipe.state))):
        np.testing.assert_array_equal(x, y)
    assert pipe.trace_count == traces, "the synchronous replay retraced"
    for c, rs in zip(requests, results):
        for b, r in zip(requests[c], rs):
            b = jax.device_get(b)
            want = [verdict[k] for k in zip(b.tuple_hash, b.ts, b.size)]
            np.testing.assert_array_equal(r.pkt_actions, want)
    log("D", compile_s=f"{compile_s:.3f}", requests=st.served_requests,
        packets=st.served, dispatches=st.dispatches, coalesced=st.coalesced,
        padded=st.padded, failed_dispatches=st.failed_dispatches,
        trace_count=svc.trace_count, p99_e2e_us=f"{st.e2e.p99:.0f}",
        device=repr(jax.devices()[0].device_kind),
        peak_bytes_in_use=_peak_bytes())


def run_phases(sizes: Sizes, seed: int) -> list:
    """Phases A-D; returns the names of the phases that failed."""
    mlp, cnn = models(seed)
    ref = Reference(mlp, cnn)
    traffic = make_traffic(sizes, seed, flows=sizes.flows, steps=sizes.steps)
    expected = oracle_run(sizes, traffic)
    failed, pipe = [], None

    def attempt(name, fn):
        try:
            return fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(name, status="FAILED")

    pipe = attempt("A", lambda: phase_pipeline(
        "A", sizes, mlp, cnn, traffic, expected, ref, use_pallas=False))
    attempt("B", lambda: phase_pipeline(
        "B", sizes, mlp, cnn, traffic, expected, Reference(mlp, cnn),
        use_pallas=True))
    attempt("C", lambda: phase_cold(sizes, mlp, cnn, seed, Reference(mlp, cnn)))
    if pipe is None:  # phase A failed: serve a fresh pipeline
        from repro.serving import OctopusPipeline

        pipe = OctopusPipeline(mlp, cnn, pipeline_config(sizes))
    attempt("D", lambda: phase_service(sizes, pipe, seed))
    return failed


def run_four_chips(sizes: Sizes, seed: int) -> None:
    """The sharded pipeline, one lane per chip, against the single lane."""
    import jax
    import numpy as np
    from repro.core import flow_tracker as ft
    from repro.serving import OctopusPipeline, ShardedOctopusPipeline
    from test_sharded import assert_residual_modulo_shard, collect_drained

    lanes = 4
    assert len(jax.devices()) == lanes, f"need {lanes} chips"
    mlp, cnn = models(seed)
    cfg = replace(pipeline_config(sizes), scan_len=1)
    single = OctopusPipeline(mlp, cnn, cfg)
    sharded = ShardedOctopusPipeline(mlp, cnn, cfg, num_shards=lanes)
    assert sharded.backend == "shard_map", sharded.backend
    homes = [s.device for s in sharded.state.count.addressable_shards]
    assert len(set(homes)) == lanes and all(
        s.data.shape[0] == 1 for s in sharded.state.count.addressable_shards)
    traffic = make_traffic(sizes, seed, flows=sizes.flows, steps=sizes.steps,
                           collision_free=True)
    t0 = time.perf_counter()
    single.warmup()
    sharded.warmup()
    compile_s = time.perf_counter() - t0
    drained_single, drained_sharded = {}, {}
    for batch in traffic:
        a, b = jax.device_get((single.step(batch), sharded.step(batch)))
        np.testing.assert_array_equal(a.pkt_actions, b.pkt_actions)
        assert int(a.new_flows) == int(b.new_flows)
        collect_drained(a, drained_single)
        collect_drained(b, drained_sharded)
        # drain timing is equal only while no lane holds back a ready flow
        assert int(ft.ready_mask(single.state, top_n=cfg.top_n).sum()) == 0
        assert int((sharded.state.count >= cfg.top_n).sum()) == 0
    assert drained_single, "no flow reached the ready threshold"
    assert drained_single == drained_sharded
    assert single.rules.rules == sharded.rules.rules
    assert_residual_modulo_shard(
        SimpleNamespace(state=jax.device_get(single.state)),
        SimpleNamespace(state=jax.device_get(sharded.state)), lanes)
    assert sharded.trace_count == 1
    log("four_chips", backend=sharded.backend, lanes=lanes,
        devices=",".join(str(d.id) for d in homes),
        compile_s=f"{compile_s:.3f}", steps=sharded.stats.steps,
        drained=sum(len(v) for v in drained_single.values()),
        padded=sharded.stats.padded, peak_bytes_in_use=_peak_bytes())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded pipeline over four chips")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: run from the root of an Octopus checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]
    from repro.runtime import platform

    cache = platform.enable_compile_cache()
    import jax
    from repro.runtime import RuntimeConfig

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if RuntimeConfig().interpret:
        print("chip_smoke: RuntimeConfig() chose Pallas interpret mode",
              file=sys.stderr)
        return 1
    log("setup", jax=jax.__version__, device_kind=dev.device_kind,
        devices=len(jax.devices()), compile_cache=cache, seed=args.seed)

    t0 = time.perf_counter()
    if args.four_chips:
        try:
            run_four_chips(Sizes(), args.seed)
            failed = []
        except Exception:
            traceback.print_exc()
            failed = ["four_chips"]
    else:
        failed = run_phases(Sizes(), args.seed)
    log("done", seconds=f"{time.perf_counter() - t0:.1f}",
        failed=",".join(failed) or "none")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
