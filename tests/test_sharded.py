"""Sharded multi-lane pipeline: differential harness proving shard/no-shard
equivalence (union of drained flows, residual tables modulo shard, per-flow
decisions — exact int32), partition_batch conservation laws (deterministic +
hypothesis), forced cross-shard-collision coverage, no-retrace/donation
checks, and vmap-vs-shard_map backend parity."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import flow_tracker as ft
from repro.data.traffic import (
    TrafficConfig,
    TrafficGenerator,
    partition_batch,
    shard_of,
)
from repro.kernels.flow_features.ops import default_program
from repro.models import paper_models
from repro.serving import OctopusPipeline, PipelineConfig, ShardedOctopusPipeline

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def params():
    return {
        "mlp": paper_models.init_paper_model("mlp", jax.random.PRNGKey(0)),
        "cnn": paper_models.init_paper_model("cnn", jax.random.PRNGKey(1)),
        "transformer": paper_models.init_paper_model("transformer",
                                                     jax.random.PRNGKey(2)),
    }


def make_batch(hashes, ts, *, size=100, pay_bytes=16):
    n = len(hashes)
    return ft.PacketBatch(
        ts=jnp.asarray(ts, jnp.int32),
        size=jnp.full((n,), size, jnp.int32),
        dir=jnp.zeros((n,), jnp.int32), flags=jnp.zeros((n,), jnp.int32),
        proto=jnp.zeros((n,), jnp.int32),
        tuple_hash=jnp.asarray(hashes, jnp.int32),
        payload=jnp.zeros((n, pay_bytes), jnp.int32))


def collect_drained(out, dst: dict):
    """Union of drained flows: tuple_id -> list of emitted snapshots (an
    elephant can cross the ready threshold several times) + decisions."""
    mask = np.asarray(out.drained.mask)
    for i in np.flatnonzero(mask):
        tid = int(out.drained.tuple_id[i])
        dst.setdefault(tid, []).append((
            int(out.drained.slots[i]), int(out.drained.count[i]),
            np.asarray(out.drained.features[i]).tolist(),
            np.asarray(out.drained.series[i]).tolist(),
            np.asarray(out.drained.sizes[i]).tolist(),
            np.asarray(out.drained.payload[i]).tolist(),
            int(out.flow_actions[i]), int(out.flow_cls[i]),
        ))


def assert_residual_modulo_shard(ref: OctopusPipeline,
                                 sh: ShardedOctopusPipeline, S: int):
    """Every live flow of the single-lane table exists bit-identically at
    the same slot of its shard's bank; any extra sharded-live row is a stale
    flow the oracle recycled by a cross-shard collision (its slot in the
    oracle table holds a different tuple)."""
    live = np.flatnonzero(np.asarray(ref.state.count) > 0)
    for slot in live:
        tid = int(ref.state.tuple_id[slot])
        lane = shard_of(tid, S)
        assert int(sh.state.tuple_id[lane, slot]) == tid
        for field in ("count", "last_ts", "features", "series", "sizes",
                      "payload"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref.state, field)[slot]),
                np.asarray(getattr(sh.state, field)[lane, slot]),
                err_msg=f"residual {field} @ slot {slot}")
    ref_live = {(int(ref.state.tuple_id[s]), int(s)) for s in live}
    sh_count = np.asarray(sh.state.count)
    for lane, slot in zip(*np.nonzero(sh_count > 0)):
        tid = int(sh.state.tuple_id[lane, slot])
        if (tid, int(slot)) not in ref_live:
            # stale leftover: the oracle's slot was recycled by another flow
            assert int(ref.state.tuple_id[slot]) != tid


def run_differential(params, num_shards, *, tracker="segmented", steps=16,
                     seed=7, lane_batch=None, scan_len=1, table_size=64):
    from dataclasses import replace

    cfg = PipelineConfig(batch_size=24, max_ready=16, flow_model="transformer",
                         table_size=table_size, top_n=6, top_k=15,
                         pay_bytes=16, tracker=tracker, scan_len=scan_len)
    ref = OctopusPipeline(params["mlp"], params["transformer"],
                          replace(cfg, scan_len=1))
    sh = ShardedOctopusPipeline(params["mlp"], params["transformer"], cfg,
                                num_shards=num_shards, lane_batch=lane_batch)

    def gen():
        return TrafficGenerator(TrafficConfig(
            batch_size=24, active_flows=12, elephant_fraction=0.5,
            table_size=table_size, seed=seed, burst_prob=0.3))

    g_ref, g_sh = gen(), gen()
    drained_ref, drained_sh = {}, {}
    if scan_len > 1:
        sh.warmup()
        for _ in range(steps // scan_len):
            batches = [g_sh.next_batch() for _ in range(scan_len)]
            out = sh.step_many(batches)
            for j in range(scan_len):
                collect_drained(jax.tree_util.tree_map(lambda a: a[j], out),
                                drained_sh)
        for _ in range(steps):
            collect_drained(ref.step(g_ref.next_batch()), drained_ref)
    else:
        for _ in range(steps):
            o_ref = ref.step(g_ref.next_batch())
            o_sh = sh.step(g_sh.next_batch())
            np.testing.assert_array_equal(np.asarray(o_ref.pkt_actions),
                                          np.asarray(o_sh.pkt_actions))
            assert int(o_ref.new_flows) == int(o_sh.new_flows)
            collect_drained(o_ref, drained_ref)
            collect_drained(o_sh, drained_sh)
            # ample budget is a precondition of drain-timing equality; make
            # it a tested invariant instead of luck
            assert int(np.asarray(
                ft.ready_mask(ref.state, top_n=cfg.top_n)).sum()) == 0
            assert int(np.asarray(sh.state.count >= cfg.top_n).sum()) == 0
    assert drained_ref, "stream never exercised the emission path"
    assert drained_ref == drained_sh
    assert ref.rules.rules == sh.rules.rules
    return ref, sh


# ------------------------------------------------------------- differential

@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_sharded_matches_single_lane_oracle(params, num_shards):
    """The issue's core acceptance: exact int32 equality of the union of
    drained flows, the residual tables (modulo shard) and every per-flow
    class decision, for num_shards in {1, 2, 4} on one seeded stream."""
    ref, sh = run_differential(params, num_shards)
    assert_residual_modulo_shard(ref, sh, num_shards)
    assert sh.trace_count == 1
    assert sh.stats.packets == ref.stats.packets  # padding never counted
    if num_shards > 1:
        assert sh.stats.padded > 0


@pytest.mark.parametrize("tracker", ["segmented", "scan"])
def test_sharded_trackers_agree(params, tracker):
    ref, sh = run_differential(params, 2, tracker=tracker, steps=10)
    assert_residual_modulo_shard(ref, sh, 2)


def test_sharded_multi_round_matches_lockstep(params):
    """A small lane_batch only changes dispatch granularity: the overflow
    rounds compose sequentially, bit-exact to the skew-proof single round."""
    cfg = PipelineConfig(batch_size=24, max_ready=8, flow_model="transformer",
                         table_size=64, top_n=6, top_k=15, pay_bytes=16)
    a = ShardedOctopusPipeline(params["mlp"], params["transformer"], cfg,
                               num_shards=4)
    b = ShardedOctopusPipeline(params["mlp"], params["transformer"], cfg,
                               num_shards=4, lane_batch=8)

    def gen():
        return TrafficGenerator(TrafficConfig(
            batch_size=24, active_flows=12, elephant_fraction=0.5,
            table_size=64, seed=7))

    ga, gb = gen(), gen()
    for _ in range(12):
        oa, ob = a.step(ga.next_batch()), b.step(gb.next_batch())
        for x, y in zip(jax.tree_util.tree_leaves(oa),
                        jax.tree_util.tree_leaves(ob)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(a.state),
                    jax.tree_util.tree_leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert b.stats.dispatches > a.stats.dispatches  # rounds actually spilled
    assert b.stats.packets == a.stats.packets  # honest packet accounting
    assert b.rules.rules == a.rules.rules


def test_forced_cross_shard_collision(params):
    """Two flows whose hashes collide mod num_shards (same shard) AND on the
    same table slot: the in-lane eviction dance must match the single-lane
    oracle bit-for-bit — the freeing rule is shard-local state, preserved by
    hash partitioning."""
    S, table = 4, 32
    h1 = 101
    h2 = next(h for h in range(h1 + S, 50_000, S)
              if ft.hash_slot_scalar(h, table) == ft.hash_slot_scalar(h1, table))
    assert shard_of(h1, S) == shard_of(h2, S)

    cfg = PipelineConfig(batch_size=8, max_ready=4, flow_model="transformer",
                         table_size=table, top_n=4, top_k=15, pay_bytes=16)
    ref = OctopusPipeline(params["mlp"], params["transformer"], cfg)
    sh = ShardedOctopusPipeline(params["mlp"], params["transformer"], cfg,
                                num_shards=S)
    # h1 sends 3 (below top_n), h2 collides and evicts, then h2 drains;
    # then h1 re-establishes over h2's drained slot
    seq = [
        make_batch([h1] * 3 + [h2] * 5, [10, 20, 30, 40, 50, 60, 70, 80]),
        make_batch([h1] * 8, [90 + 10 * i for i in range(8)]),
    ]
    drained_ref, drained_sh = {}, {}
    for batch in seq:
        o_ref, o_sh = ref.step(batch), sh.step(batch)
        np.testing.assert_array_equal(np.asarray(o_ref.pkt_actions),
                                      np.asarray(o_sh.pkt_actions))
        assert int(o_ref.new_flows) == int(o_sh.new_flows)
        assert int(o_ref.evicted) == int(o_sh.evicted)
        # drained-row ORDER may differ (lane-major vs slot-major); the union
        # of emitted snapshots must not
        collect_drained(o_ref, drained_ref)
        collect_drained(o_sh, drained_sh)
    assert drained_ref == drained_sh and set(drained_ref) == {h1, h2}
    assert ref.stats.evicted == sh.stats.evicted > 0  # the collision fired
    assert ref.stats.flows == sh.stats.flows >= 2  # both flows drained
    lane = shard_of(h1, S)
    for x, y in zip(jax.tree_util.tree_leaves(ref.state),
                    jax.tree_util.tree_leaves(sh.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y[lane]))


def test_sharded_chunked_dispatch_matches_per_step(params):
    """scan_len > 1 over the sharded step: same drained union and rule table
    as the per-step sharded run, one trace, steps/scan_len dispatches."""
    ref, sh = run_differential(params, 2, scan_len=4, steps=12)
    assert sh.trace_count == 1
    assert sh.stats.dispatches == 3 and sh.stats.steps == 12
    assert sh.stats.packets == 12 * 24
    # padded counts per step: lockstep lanes pad (S*C - B) rows each step
    assert sh.stats.padded == 12 * (2 * 24 - 24)


# ------------------------------------------------- partition conservation

def check_partition_conservation(batch: ft.PacketBatch, num_shards: int,
                                 lane_batch=None):
    """Shared invariant checker: every valid packet appears in exactly one
    shard/round with keep set, on the lane shard_of names, in arrival order;
    padding rows are zeroed with src == P."""
    n = int(np.asarray(batch.ts).shape[0])
    hashes = np.asarray(batch.tuple_hash)
    rounds = partition_batch(batch, num_shards, lane_batch=lane_batch)
    seen = []
    for sb in rounds:
        keep = np.asarray(sb.keep)
        src = np.asarray(sb.src)
        for lane in range(num_shards):
            idx = src[lane][keep[lane]]
            seen.extend(idx.tolist())
            # lane assignment is a pure function of tuple_hash
            np.testing.assert_array_equal(shard_of(hashes[idx], num_shards),
                                          lane)
            # kept rows carry the original packet fields verbatim
            for f_src, f_dst in zip(batch, sb.shards):
                np.testing.assert_array_equal(
                    np.asarray(f_src)[idx], np.asarray(f_dst)[lane][keep[lane]])
            # padding rows are inert: zeroed fields, sentinel src
            pad = ~keep[lane]
            assert (src[lane][pad] == n).all()
            for f_dst in sb.shards:
                assert (np.asarray(f_dst)[lane][pad] == 0).all()
        # per-lane arrival order is preserved within and across rounds
    assert sorted(seen) == list(range(n))  # exactly-once conservation
    for lane in range(num_shards):
        lane_order = [i for sb in rounds
                      for i in np.asarray(sb.src)[lane][np.asarray(sb.keep)[lane]]]
        assert lane_order == sorted(lane_order)
    return rounds


def random_batch(rng, n, pool, pay_bytes=4):
    return ft.PacketBatch(
        ts=jnp.asarray(np.cumsum(rng.integers(1, 50, n)).astype(np.int32)),
        size=jnp.asarray(rng.integers(40, 1500, n).astype(np.int32)),
        dir=jnp.asarray(rng.integers(0, 2, n).astype(np.int32)),
        flags=jnp.asarray(rng.integers(0, 64, n).astype(np.int32)),
        proto=jnp.asarray(rng.integers(0, 3, n).astype(np.int32)),
        tuple_hash=jnp.asarray(rng.choice(pool, n).astype(np.int32)),
        payload=jnp.asarray(rng.integers(0, 256, (n, pay_bytes)).astype(np.int32)))


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("num_shards", [1, 2, 3, 4])
def test_partition_conservation_seeded(seed, num_shards):
    rng = np.random.default_rng(seed)
    batch = random_batch(rng, 32, np.arange(1, 20))
    check_partition_conservation(batch, num_shards)
    check_partition_conservation(batch, num_shards, lane_batch=8)


def test_partition_validates_arguments():
    rng = np.random.default_rng(0)
    batch = random_batch(rng, 8, np.arange(1, 5))
    with pytest.raises(ValueError):
        partition_batch(batch, 0)
    with pytest.raises(ValueError):
        partition_batch(batch, 2, lane_batch=0)
    with pytest.raises(ValueError):
        partition_batch(batch, 2, lane_batch=9)


def test_shard_of_is_pure_and_host_device_consistent():
    rng = np.random.default_rng(0)
    hashes = rng.integers(1, 2**31 - 1, 200).astype(np.int32)
    for S in (1, 2, 3, 4, 8):
        dev = np.asarray(shard_of(jnp.asarray(hashes), S))
        host = shard_of(hashes, S)
        scalar = [shard_of(int(h), S) for h in hashes]
        np.testing.assert_array_equal(dev, host)
        np.testing.assert_array_equal(dev, scalar)
        assert (dev >= 0).all() and (dev < S).all()


def check_sharded_count_monotonicity(seed: int, num_shards: int,
                                     n_batches: int = 6, batch: int = 16,
                                     table_size: int = 32, top_n: int = 4):
    """Re-merge invariant (the sharded sibling of
    test_flow_tracker_props.check_stream_invariants): feeding each lane its
    partition keeps every flow's count identical to the unsharded tracker
    and monotone across batches — summed over lanes, nothing is lost or
    double-counted."""
    rng = np.random.default_rng(seed)
    program = default_program()
    # collision-free pool: distinct slots so lane-local state == global state
    pool, used = [], set()
    for h in range(1, 10_000):
        s = ft.hash_slot_scalar(h, table_size)
        if s not in used:
            used.add(s)
            pool.append(h)
        if len(pool) == 8:
            break
    ref = ft.init_state(table_size, top_n, 3, 4)
    lanes = [ft.init_state(table_size, top_n, 3, 4) for _ in range(num_shards)]
    last_counts: dict[int, int] = {}
    for _ in range(n_batches):
        b = random_batch(rng, batch, np.asarray(pool))
        ref, _ = ft.process_packets(ref, b, program, top_n=top_n)
        for sb in partition_batch(b, num_shards):
            for lane in range(num_shards):
                pkts = jax.tree_util.tree_map(lambda a: a[lane], sb.shards)
                lanes[lane], _ = ft.process_packets(
                    lanes[lane], pkts, program, top_n=top_n,
                    keep=sb.keep[lane])
        ref_count = np.asarray(ref.count)
        merged = np.zeros_like(ref_count)
        for lane_state in lanes:
            merged += np.asarray(lane_state.count)
        np.testing.assert_array_equal(ref_count, merged)
        for h in pool:
            s = ft.hash_slot_scalar(h, table_size)
            c = int(ref_count[s])
            assert c >= last_counts.get(s, 0)  # count monotone under re-merge
            last_counts[s] = c


@pytest.mark.parametrize("seed", range(3))
def test_sharded_count_monotonicity_seeded(seed):
    check_sharded_count_monotonicity(seed, num_shards=seed % 3 + 2)


# --------------------------------------------------------- hypothesis (CI)

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), num_shards=st.integers(1, 5),
       n=st.integers(1, 48))
def test_partition_conservation_property(seed, num_shards, n):
    rng = np.random.default_rng(seed)
    batch = random_batch(rng, n, np.arange(1, 30))
    check_partition_conservation(batch, num_shards)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), num_shards=st.integers(1, 4),
       lane_frac=st.integers(1, 4))
def test_partition_rounds_property(seed, num_shards, lane_frac):
    rng = np.random.default_rng(seed)
    n = 32
    batch = random_batch(rng, n, np.arange(1, 12))
    check_partition_conservation(batch, num_shards,
                                 lane_batch=max(1, n // lane_frac))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), num_shards=st.integers(2, 4))
def test_sharded_count_monotonicity_property(seed, num_shards):
    check_sharded_count_monotonicity(seed, num_shards, n_batches=4)


# --------------------------------------------- retrace / donation / backends

def test_sharded_no_retrace_and_state_sustained(params):
    """One trace across sharded steps; per-shard TrackerState is donated to
    the jit'd step and carried — a flow split across global microbatches
    still reaches the ready threshold inside its lane."""
    cfg = PipelineConfig(batch_size=4, max_ready=4, flow_model="transformer",
                         table_size=16, top_n=8, top_k=15, pay_bytes=16)
    sh = ShardedOctopusPipeline(params["mlp"], params["transformer"], cfg,
                                num_shards=2)
    sh.warmup()
    assert sh.trace_count == 1
    assert all(d == 0 for d in
               (sh.stats.steps, sh.stats.dispatches))  # warmup is untimed

    h = 77
    out1 = sh.step(make_batch([h] * 4, [100, 110, 120, 130]))
    assert int(np.asarray(out1.drained.mask).sum()) == 0
    old_state = sh.state
    out2 = sh.step(make_batch([h] * 4, [140, 150, 160, 170]))
    mask = np.asarray(out2.drained.mask)
    assert int(mask.sum()) == 1
    row = int(np.flatnonzero(mask)[0])
    assert int(out2.drained.tuple_id[row]) == h
    assert int(out2.drained.count[row]) == 8
    assert row // sh.lane_ready == shard_of(h, 2)  # drained from its lane
    assert sh.trace_count == 1  # cache hits only: no per-step retrace
    # the state argument is donated: the previous buffers are consumed by
    # the dispatch (deleted) wherever the backend supports donation
    del old_state
    assert sh.stats.steps == 2 and sh.stats.packets == 8


def test_step_many_dispatches_every_overflow_round(params):
    """Regression: with lane_batch < batch_size and scan_len == 1 (the only
    chunked shape the constructor allows for multi-round mode), step_many
    must not drop the overflow rounds — skewed batches whose packets all
    land in one lane keep every packet."""
    cfg = PipelineConfig(batch_size=8, max_ready=2, flow_model="transformer",
                         table_size=16, top_n=8, top_k=15, pay_bytes=16)
    sh = ShardedOctopusPipeline(params["mlp"], params["transformer"], cfg,
                                num_shards=2, lane_batch=2)
    h = 4  # even: every packet lands in lane 0 -> 4 overflow rounds
    assert shard_of(h, 2) == 0
    out = sh.step_many([make_batch([h] * 8, [10 * i for i in range(1, 9)])])
    assert out.pkt_actions.shape == (1, 8)  # stacked like the lockstep path
    assert int(np.asarray(out.drained.mask).sum()) == 1  # all 8 pkts tracked
    assert sh.stats.steps == 1 and sh.stats.packets == 8
    assert sh.stats.dispatches == 4  # the rounds actually dispatched


@pytest.mark.parametrize("hashes", [[4] * 12 + [1, 2, 3, 5], list(range(16))],
                         ids=["skewed", "even"])
def test_lane_rows_max_counts_the_fullest_lane(params, hashes):
    """``lane_rows_max`` adds each dispatch's fullest lane's kept rows, as
    the hash partition lays them out, on the masked and the fused path."""
    cfg = PipelineConfig(batch_size=16, max_ready=4, flow_model="cnn",
                         table_size=64)
    sh = ShardedOctopusPipeline(params["mlp"], params["cnn"], cfg,
                                num_shards=4)
    batch = make_batch(hashes, list(range(1, 17)))
    keep = np.arange(16) % 5 != 0

    def fullest(k):
        rows = np.asarray(partition_batch(batch, 4, keep=k)[0].keep)
        return int(rows.sum(axis=1).max())

    sh.step_masked(batch, keep)
    assert sh.stats.lane_rows_max == fullest(keep)
    sh.step(batch)
    assert sh.stats.lane_rows_max == fullest(keep) + fullest(None)
    st = sh.stats
    skew = 4 * st.lane_rows_max / st.packets
    assert (skew == 1.0) == (hashes == list(range(16)))
    assert st.packets / (st.packets + st.padded) == st.packets / (4 * 16 * 2)


def test_sharded_step_rejects_wrong_batch_size(params):
    cfg = PipelineConfig(batch_size=8, max_ready=2, flow_model="cnn",
                         table_size=64)
    sh = ShardedOctopusPipeline(params["mlp"], params["cnn"], cfg,
                                num_shards=2)
    with pytest.raises(ValueError, match="batch_size"):
        sh.step(make_batch([1] * 4, [1, 2, 3, 4]))


def test_sharded_config_validation(params):
    cfg = PipelineConfig(batch_size=8, max_ready=4, flow_model="cnn",
                         table_size=64)
    with pytest.raises(ValueError, match="num_shards"):
        ShardedOctopusPipeline(params["mlp"], params["cnn"], cfg, num_shards=0)
    with pytest.raises(ValueError, match="divide"):
        ShardedOctopusPipeline(params["mlp"], params["cnn"], cfg, num_shards=3)
    with pytest.raises(ValueError, match="lane_batch"):
        ShardedOctopusPipeline(params["mlp"], params["cnn"], cfg, num_shards=2,
                               lane_batch=9)
    with pytest.raises(ValueError, match="backend"):
        ShardedOctopusPipeline(params["mlp"], params["cnn"], cfg, num_shards=2,
                               backend="pmap")
    chunked = PipelineConfig(batch_size=8, max_ready=4, flow_model="cnn",
                             table_size=64, scan_len=2)
    with pytest.raises(ValueError, match="lane_batch"):
        ShardedOctopusPipeline(params["mlp"], params["cnn"], chunked,
                               num_shards=2, lane_batch=4)


def test_sharded_explain_scopes_lanes(params):
    cfg = PipelineConfig(batch_size=16, max_ready=4, flow_model="cnn",
                         table_size=64)
    sh = ShardedOctopusPipeline(params["mlp"], params["cnn"], cfg,
                                num_shards=2)
    plan = sh.plan()
    assert len(plan.scoped("lane0")) == len(plan.scoped("lane1")) == 9
    assert len(plan.scoped("lane0").scoped("lane0/pkt")) == 4
    text = sh.explain()
    assert "lanes=2" in text and "lane_batch=16" in text
    assert "lane0: 4 pkt + 5 flow matmuls" in text
    assert "lane1:" in text


@pytest.mark.skipif(jax.local_device_count() < 2,
                    reason="shard_map parity needs >= 2 devices")
def test_vmap_vs_shard_map_parity_direct(params):
    """On multi-device hosts the two lane backends must be bit-identical."""
    cfg = PipelineConfig(batch_size=16, max_ready=4, flow_model="cnn",
                         table_size=64)
    gen = lambda: TrafficGenerator(TrafficConfig(
        batch_size=16, active_flows=8, elephant_fraction=0.5, table_size=64,
        seed=3))
    a = ShardedOctopusPipeline(params["mlp"], params["cnn"], cfg,
                               num_shards=2, backend="vmap")
    b = ShardedOctopusPipeline(params["mlp"], params["cnn"], cfg,
                               num_shards=2, backend="shard_map")
    ga, gb = gen(), gen()
    for _ in range(6):
        oa, ob = a.step(ga.next_batch()), b.step(gb.next_batch())
        for x, y in zip(jax.tree_util.tree_leaves(oa),
                        jax.tree_util.tree_leaves(ob)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(a.state),
                    jax.tree_util.tree_leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.slow
def test_vmap_vs_shard_map_parity_subprocess():
    """Force 4 host devices in a subprocess (the flag must precede jax init)
    and assert the shard_map lanes match the vmap lanes bit-for-bit."""
    code = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.data.traffic import TrafficConfig, TrafficGenerator
    from repro.models import paper_models
    from repro.runtime import platform
    from repro.serving import PipelineConfig, ShardedOctopusPipeline

    assert jax.local_device_count() == 4
    assert platform.lanes_backend(4) == "shard_map"
    pm = paper_models.init_paper_model("mlp", jax.random.PRNGKey(0))
    pc = paper_models.init_paper_model("cnn", jax.random.PRNGKey(1))
    cfg = PipelineConfig(batch_size=16, max_ready=4, flow_model="cnn",
                         table_size=64)
    gen = lambda: TrafficGenerator(TrafficConfig(
        batch_size=16, active_flows=8, elephant_fraction=0.5, table_size=64,
        seed=3))
    a = ShardedOctopusPipeline(pm, pc, cfg, num_shards=4, backend="vmap")
    b = ShardedOctopusPipeline(pm, pc, cfg, num_shards=4)  # auto: shard_map
    assert b.backend == "shard_map"
    ga, gb = gen(), gen()
    for _ in range(6):
        oa, ob = a.step(ga.next_batch()), b.step(gb.next_batch())
        for x, y in zip(jax.tree_util.tree_leaves(oa),
                        jax.tree_util.tree_leaves(ob)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(a.state),
                    jax.tree_util.tree_leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a.rules.rules == b.rules.rules
    print("OK shard_map == vmap")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    assert "OK shard_map == vmap" in out.stdout
