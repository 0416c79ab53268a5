"""Two-level (hot/cold) flow table: differential tests against a pure-Python
oracle that mirrors the device step semantics one-for-one (promote -> merge
with spill capture -> sequential cold inserts -> scrub -> drain), spill-record
parity between the scan and segmented trackers, hot-only bit-equivalence
(``cold_size > 0`` with collision-free traffic == single-level pipeline),
eviction-policy unit tests, a spill/promote roundtrip proving flow history
survives eviction, shard/no-shard equivalence with per-lane cold banks, and
the compacted walks' edge cases (one serial trip per acting record)."""
import copy
import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_states_equal
from test_pipeline import OracleTracker, batch_as_dicts

from repro.core import cold_store, flow_tracker as ft
from repro.core import feature_extractor as fe
from repro.data.traffic import TrafficConfig, TrafficGenerator, shard_of
from repro.kernels.flow_features.ops import default_program
from repro.models import paper_models
from repro.serving import OctopusPipeline, PipelineConfig, ShardedOctopusPipeline


@pytest.fixture(scope="module")
def params():
    return {
        "mlp": paper_models.init_paper_model("mlp", jax.random.PRNGKey(0)),
        "transformer": paper_models.init_paper_model("transformer",
                                                     jax.random.PRNGKey(2)),
    }


def make_batch(hashes, ts, sizes=None, *, pay_bytes=16):
    n = len(hashes)
    sizes = [100] * n if sizes is None else sizes
    return ft.PacketBatch(
        ts=jnp.asarray(ts, jnp.int32),
        size=jnp.asarray(sizes, jnp.int32),
        dir=jnp.zeros((n,), jnp.int32), flags=jnp.zeros((n,), jnp.int32),
        proto=jnp.zeros((n,), jnp.int32),
        tuple_hash=jnp.asarray(hashes, jnp.int32),
        payload=jnp.zeros((n, pay_bytes), jnp.int32))


def hash_for_slot(slot: int, table_size: int, start: int = 1) -> int:
    return next(h for h in range(start, 10**7)
                if ft.hash_slot_scalar(h, table_size) == slot)


# ---------------------------------------------------------------------------
# Pure-Python two-level oracle: OracleTracker (the hot half) + a cold dict,
# mirroring repro.core.cold_store's documented step semantics exactly.
# ---------------------------------------------------------------------------

class TwoLevelOracle(OracleTracker):
    def __init__(self, table_size, cold_size, top_n, top_k, pay_bytes,
                 policy="age"):
        super().__init__(table_size, top_n, top_k, pay_bytes)
        self.cold_size = cold_size
        self.policy = policy
        self.cold: dict[int, dict] = {}  # cold slot -> entry dict + "stamp"
        self.tick = 0
        self.spilled = 0
        self.promoted = 0

    def _cold_find(self, h):
        a, b = cold_store.cold_slots_scalar(h, self.cold_size)
        if a in self.cold and self.cold[a]["tuple_id"] == h:
            return a
        if b in self.cold and self.cold[b]["tuple_id"] == h:
            return b
        return None

    def _cold_insert(self, entry):
        """Mirror of _choose_slot + one spill insert: own entry -> first empty
        candidate -> smaller stamp (tie prefers candidate a)."""
        h = entry["tuple_id"]
        a, b = cold_store.cold_slots_scalar(h, self.cold_size)
        ea, eb = self.cold.get(a), self.cold.get(b)
        if ea is not None and ea["tuple_id"] == h:
            dst = a
        elif eb is not None and eb["tuple_id"] == h:
            dst = b
        elif ea is None:
            dst = a
        elif eb is None:
            dst = b
        else:
            dst = a if ea["stamp"] <= eb["stamp"] else b
        entry = copy.deepcopy(entry)
        entry["stamp"] = entry["last_ts"] if self.policy == "age" else self.tick
        self.cold[dst] = entry
        self.tick += 1

    def step_batch(self, batch_dicts, max_ready):
        # 1. promote: segment heads, ascending hot-slot order
        heads = {}
        for pkt in batch_dicts:
            s = self.slot_of(pkt["tuple_hash"])
            heads.setdefault(s, pkt["tuple_hash"])
        for s in sorted(heads):
            h = heads[s]
            e = self.slots.get(s)
            if e is not None and e["tuple_id"] == h:
                continue  # already live in hot
            src = self._cold_find(h)
            if src is None:
                continue
            entry = self.cold.pop(src)
            if e is not None:  # displaced occupant spills (after src freed)
                self._cold_insert(e)
            entry.pop("stamp")
            self.slots[s] = entry
            self.promoted += 1
        # 2. merge with spill capture, in packet order
        spills = []
        for pkt in batch_dicts:
            s = self.slot_of(pkt["tuple_hash"])
            e = self.slots.get(s)
            if e is not None and e["tuple_id"] != pkt["tuple_hash"]:
                spills.append(copy.deepcopy(e))
            self.process(pkt)
        # 3. cold inserts, sequential in packet order
        for rec in spills:
            self._cold_insert(rec)
            self.spilled += 1
        # 4. scrub: no tuple live in hot may stay in cold
        for pkt in batch_dicts:
            h = pkt["tuple_hash"]
            e = self.slots.get(self.slot_of(h))
            if e is not None and e["tuple_id"] == h:
                c = self._cold_find(h)
                if c is not None:
                    del self.cold[c]
        # 5. drain (hot only)
        return self.drain_ready(max_ready)


def assert_drained_equal(out, expect, oracle):
    d = out.drained
    assert int(np.asarray(d.mask).sum()) == len(expect)
    for r, want in enumerate(expect):
        assert int(d.slots[r]) == want["slot"]
        assert int(d.tuple_id[r]) == want["tuple_id"]
        assert int(d.count[r]) == want["count"]
        np.testing.assert_array_equal(np.asarray(d.features[r]),
                                      np.asarray(want["features"], np.int32))
        np.testing.assert_array_equal(np.asarray(d.series[r]),
                                      np.asarray(want["series"], np.int32))
        np.testing.assert_array_equal(np.asarray(d.sizes[r]),
                                      np.asarray(want["sizes"], np.int32))
        np.testing.assert_array_equal(np.asarray(d.payload[r]),
                                      np.asarray(want["payload"], np.int32))


def assert_two_level_state_equal(state, oracle):
    hot, cold = state.hot, state.cold
    live = set(np.flatnonzero(np.asarray(hot.count) > 0).tolist())
    assert live == set(oracle.slots)
    for s in live:
        e = oracle.slots[s]
        assert int(hot.tuple_id[s]) == e["tuple_id"]
        assert int(hot.count[s]) == e["count"]
        np.testing.assert_array_equal(
            np.asarray(hot.features[s]),
            np.asarray(oracle.feature_word(e), np.int32))
        np.testing.assert_array_equal(np.asarray(hot.series[s]),
                                      np.asarray(e["series"], np.int32))
    occ = set(np.flatnonzero(np.asarray(cold.count) > 0).tolist())
    assert occ == set(oracle.cold)
    for c in occ:
        e = oracle.cold[c]
        assert int(cold.tuple_id[c]) == e["tuple_id"]
        assert int(cold.count[c]) == e["count"]
        assert int(cold.stamp[c]) == e["stamp"]
        np.testing.assert_array_equal(
            np.asarray(cold.features[c]),
            np.asarray(oracle.feature_word(e), np.int32))
    assert int(cold.tick) == oracle.tick


# ---------------------------------------------------------------------------
# Hashing + insert policy
# ---------------------------------------------------------------------------

def test_cold_slots_scalar_matches_array():
    rng = np.random.default_rng(0)
    hashes = np.concatenate([
        rng.integers(1, 2**31 - 1, size=256),
        rng.integers(-(2**31), 0, size=64), [0, 1, -1, 2**31 - 1]])
    for cold_size in (2, 64, 1 << 17):
        a, b = cold_store.cold_slots(jnp.asarray(hashes, jnp.int32), cold_size)
        for i, h in enumerate(hashes):
            sa, sb = cold_store.cold_slots_scalar(int(h), cold_size)
            assert (int(a[i]), int(b[i])) == (sa, sb)


def _spill(h, count, ts, *, top_n=2, top_k=2, pay_bytes=2):
    one = lambda v, shape=(1,): jnp.full(shape, v, jnp.int32)  # noqa: E731
    return ft.SpillRecords(
        mask=jnp.ones((1,), bool), slot=one(0),
        tuple_id=one(h), count=one(count), last_ts=one(ts),
        features=one(0, (1, 16)), series=one(0, (1, top_n)),
        sizes=one(0, (1, top_n)), payload=one(0, (1, top_k, pay_bytes)))


def _find_hash_with_cold_slots(want_a, want_b, cold_size, start=1):
    return next(h for h in range(start, 10**7)
                if cold_store.cold_slots_scalar(h, cold_size) == (want_a,
                                                                  want_b))


@pytest.mark.parametrize("policy,evicted_slot", [("age", 1), ("lru", 0)])
def test_insert_eviction_policy(policy, evicted_slot):
    """Full cold table, third insert: age evicts the longest-idle entry
    (smaller last_ts, slot 1 here), lru the earliest-inserted (slot 0)."""
    C = 2
    h1 = _find_hash_with_cold_slots(0, 1, C)
    h2 = _find_hash_with_cold_slots(1, 0, C, start=h1 + 1)
    h3 = _find_hash_with_cold_slots(0, 1, C, start=h2 + 1)
    cold = cold_store.init_cold(C, top_n=2, top_k=2, pay_bytes=2)
    cold, n1 = cold_store.apply_spills(cold, _spill(h1, 3, ts=100),
                                       policy=policy)
    cold, n2 = cold_store.apply_spills(cold, _spill(h2, 4, ts=50),
                                       policy=policy)
    assert int(n1) == int(n2) == 1
    assert int(cold.tuple_id[0]) == h1 and int(cold.tuple_id[1]) == h2
    cold, _ = cold_store.apply_spills(cold, _spill(h3, 5, ts=200),
                                      policy=policy)
    assert int(cold.tuple_id[evicted_slot]) == h3
    survivor = h2 if evicted_slot == 0 else h1
    assert int(cold.tuple_id[1 - evicted_slot]) == survivor
    assert int(cold.tick) == 3


def test_insert_overwrites_own_entry_never_duplicates():
    C = 64
    cold = cold_store.init_cold(C, top_n=2, top_k=2, pay_bytes=2)
    h = 1234
    cold, _ = cold_store.apply_spills(cold, _spill(h, 3, ts=10), policy="age")
    cold, _ = cold_store.apply_spills(cold, _spill(h, 7, ts=20), policy="age")
    assert int(cold_store.cold_occupancy(cold)) == 1
    a, _b = cold_store.cold_slots_scalar(h, C)
    assert int(cold.count[a]) == 7 and int(cold.last_ts[a]) == 20


def test_masked_spill_is_noop():
    cold = cold_store.init_cold(8, top_n=2, top_k=2, pay_bytes=2)
    sp = _spill(99, 3, ts=10)._replace(mask=jnp.zeros((1,), bool))
    cold2, n = cold_store.apply_spills(cold, sp, policy="lru")
    assert int(n) == 0
    assert_states_equal(cold, cold2)


# ---------------------------------------------------------------------------
# Spill-record parity: scan tracker vs segmented tracker, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_spill_records_scan_vs_segmented(seed):
    rng = np.random.default_rng(seed)
    table, top_n, P = 16, 4, 32
    program = default_program()
    st_a = ft.init_state(table, top_n, top_k=3, pay_bytes=4)
    st_b = st_a
    pool = rng.integers(1, 10_000, size=40)
    clock = 0
    for rnd in range(6):
        hashes = rng.choice(pool, size=P)
        ts = clock + np.cumsum(rng.integers(1, 30, size=P))
        clock = int(ts[-1])
        batch = make_batch(hashes, ts, rng.integers(40, 1500, size=P).tolist(),
                           pay_bytes=4)
        keep = (None if rnd % 2 == 0
                else jnp.asarray(rng.random(P) < 0.8))
        st_a, out_a, sp_a = ft.process_packets(
            st_a, batch, program, top_n=top_n, keep=keep, with_spills=True)
        st_b, out_b, sp_b = fe.segmented_update(
            st_b, batch, top_n=top_n, keep=keep, with_spills=True)
        assert_states_equal(st_a, st_b)
        for name, fa, fb in zip(ft.SpillRecords._fields, sp_a, sp_b):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb),
                                          err_msg=f"spill field {name}")
        # padding convention: masked-off rows are all-zero with sentinel slot
        m = np.asarray(sp_a.mask)
        np.testing.assert_array_equal(np.asarray(sp_a.slot)[~m], table)
        np.testing.assert_array_equal(np.asarray(sp_a.tuple_id)[~m], 0)


# ---------------------------------------------------------------------------
# Spill/promote roundtrip: eviction no longer loses flow history
# ---------------------------------------------------------------------------

def test_promote_roundtrip_preserves_history(params):
    cfg = PipelineConfig(batch_size=1, max_ready=4, flow_model="transformer",
                         table_size=8, top_n=4, top_k=15, pay_bytes=16,
                         cold_size=32)
    pipe = OctopusPipeline(params["mlp"], params["transformer"], cfg)
    base = OctopusPipeline(params["mlp"], params["transformer"],
                           replace(cfg, cold_size=0))
    h1 = 1
    h2 = next(h for h in range(2, 10**6)
              if ft.hash_slot_scalar(h, 8) == ft.hash_slot_scalar(h1, 8))
    oracle = TwoLevelOracle(8, 32, top_n=4, top_k=15, pay_bytes=16)
    stream = [(h1, 10, 100), (h1, 20, 200), (h1, 30, 300),  # 3 pkts of h1
              (h2, 40, 400),  # collides: h1 spills to cold
              (h1, 50, 500),  # h1 promotes back (h2 spills), 4th pkt -> ready
              (h2, 60, 150)]  # h2 promotes back in turn
    drained = []
    for h, ts, size in stream:
        batch = make_batch([h], [ts], [size])
        expect = oracle.step_batch(batch_as_dicts(batch), cfg.max_ready)
        out = pipe.step(batch)
        base.step(batch)
        assert_drained_equal(out, expect, oracle)
        drained += expect
    # the evicted-then-promoted flow drains with its FULL history intact
    assert [d["tuple_id"] for d in drained] == [h1]
    assert drained[0]["count"] == 4
    assert drained[0]["sizes"] == [100, 200, 300, 500]
    assert drained[0]["series"] == [0, 10, 10, 20]  # pre-spill intervals kept
    assert pipe.stats.spilled == oracle.spilled == 1  # h2's displacement into
    assert pipe.stats.promoted == oracle.promoted == 2  # cold is not a spill
    # the single-level pipeline restarted h1 from scratch and drained nothing
    assert base.stats.flows == 0 and base.stats.evicted == 3
    assert_two_level_state_equal(pipe.state, oracle)


# ---------------------------------------------------------------------------
# Big differential: collision storm vs the oracle, both trackers x policies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tracker", ["segmented", "scan"])
@pytest.mark.parametrize("policy", ["age", "lru"])
def test_two_level_matches_oracle(params, tracker, policy):
    """Populations ~3x the hot table under collision_free=False traffic: the
    device two-level tracker must agree with the oracle on every drained
    flow, the residual hot table, the cold table (stamps and tick included),
    and the spill/promote totals — hot+cold never loses a flow the oracle
    keeps."""
    cfg = PipelineConfig(batch_size=24, max_ready=6, flow_model="transformer",
                         table_size=16, top_n=6, top_k=15, pay_bytes=16,
                         tracker=tracker, cold_size=64, cold_policy=policy)
    pipe = OctopusPipeline(params["mlp"], params["transformer"], cfg)
    gen = TrafficGenerator(TrafficConfig(
        batch_size=24, active_flows=48, elephant_fraction=0.5,
        table_size=16, seed=13, burst_prob=0.3, collision_free=False))
    oracle = TwoLevelOracle(16, 64, top_n=6, top_k=15, pay_bytes=16,
                            policy=policy)
    for _ in range(20):
        batch = gen.next_batch()
        expect = oracle.step_batch(batch_as_dicts(batch), cfg.max_ready)
        out = pipe.step(batch)
        assert_drained_equal(out, expect, oracle)
    assert_two_level_state_equal(pipe.state, oracle)
    assert pipe.stats.spilled == oracle.spilled
    assert pipe.stats.promoted == oracle.promoted
    assert pipe.stats.spilled > 50 and pipe.stats.promoted > 50  # a real storm
    assert pipe.trace_count == 1  # the cold path compiles once, like hot-only


# ---------------------------------------------------------------------------
# Hot-only equivalence: attaching a cold table must not perturb the hot path
# ---------------------------------------------------------------------------

def test_cold_attached_is_bit_identical_on_collision_free_traffic(params):
    cfg = PipelineConfig(batch_size=24, max_ready=4, flow_model="transformer",
                         table_size=64, top_n=6, top_k=15, pay_bytes=16)
    mk = lambda c: OctopusPipeline(params["mlp"], params["transformer"], c)  # noqa: E731
    base, two = mk(cfg), mk(replace(cfg, cold_size=512))

    def gen():
        return TrafficGenerator(TrafficConfig(
            batch_size=24, active_flows=16, elephant_fraction=0.5,
            table_size=64, seed=11, burst_prob=0.3))

    g0, g1 = gen(), gen()
    for _ in range(20):
        out0, out1 = base.step(g0.next_batch()), two.step(g1.next_batch())
        for name, a, b in zip(out0._fields, out0, out1):
            if name in ("spilled", "promoted", "cold_walk"):
                continue
            jax.tree_util.tree_map(
                lambda x, y: np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y)), a, b)
    assert_states_equal(base.state, two.state.hot)
    assert two.stats.promoted == 0  # nothing live ever sat in cold
    assert base.trace_count == two.trace_count == 1


def test_hot_only_state_is_plain_tracker_state(params):
    cfg = PipelineConfig(batch_size=8, max_ready=4, flow_model="transformer",
                         table_size=16, top_n=4, top_k=15, pay_bytes=16)
    pipe = OctopusPipeline(params["mlp"], params["transformer"], cfg)
    assert isinstance(pipe.state, ft.TrackerState)  # no cold leaves to carry
    assert "cold" not in pipe.explain()
    two = OctopusPipeline(params["mlp"], params["transformer"],
                          replace(cfg, cold_size=128, cold_policy="lru"))
    assert isinstance(two.state, cold_store.TwoLevelState)
    assert "cold=128(lru)" in two.explain()


def test_config_validates_cold_knobs():
    with pytest.raises(ValueError, match="cold_size"):
        PipelineConfig(cold_size=-1)
    with pytest.raises(ValueError, match="policy"):
        PipelineConfig(cold_size=8, cold_policy="fifo")


# ---------------------------------------------------------------------------
# Sharded: per-lane cold banks match the single-lane pipeline on one shard
# ---------------------------------------------------------------------------

def test_sharded_two_level_matches_single_lane(params):
    """All flows steered to shard 0 of a 2-lane pipeline (with forced hot
    collisions inside the shard): lane 0's hot+cold banks and the drain
    stream must be bit-identical to an unsharded pipeline fed the same
    packets, and lane 1 must stay untouched."""
    S, table = 2, 16
    cfg = PipelineConfig(batch_size=24, max_ready=16, flow_model="transformer",
                         table_size=table, top_n=4, top_k=15, pay_bytes=16,
                         cold_size=64)
    ref = OctopusPipeline(params["mlp"], params["transformer"], cfg)
    sh = ShardedOctopusPipeline(params["mlp"], params["transformer"], cfg,
                                num_shards=S)
    assert f"cold=64x{S}" in sh.explain()

    # hashes in shard 0, grouped into colliding pairs on 6 hot slots
    cand = np.arange(1, 40_000, dtype=np.int64)
    in_shard = cand[np.asarray(shard_of(jnp.asarray(cand, jnp.int32), S)) == 0]
    by_slot: dict[int, list] = {}
    for h in in_shard.tolist():
        by_slot.setdefault(ft.hash_slot_scalar(h, table), []).append(h)
    pairs = [by_slot[s][:2] for s in sorted(by_slot) if len(by_slot[s]) >= 2]
    flows = [h for pair in pairs[:6] for h in pair]  # 12 flows, 6 hot slots

    rng = np.random.default_rng(5)
    clock = 0
    for _ in range(12):
        hashes = rng.choice(flows, size=cfg.batch_size)
        ts = clock + np.cumsum(rng.integers(1, 20, size=cfg.batch_size))
        clock = int(ts[-1])
        batch = make_batch(hashes.tolist(), ts.tolist(),
                           rng.integers(40, 1500, size=cfg.batch_size).tolist())
        out_r, out_s = ref.step(batch), sh.step(batch)
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)), out_r.drained, out_s.drained)
        assert int(out_r.spilled) == int(out_s.spilled)
        assert int(out_r.promoted) == int(out_s.promoted)
    lane0 = jax.tree_util.tree_map(lambda a: a[0], sh.state)
    assert_states_equal(ref.state.hot, lane0.hot)
    assert_states_equal(ref.state.cold, lane0.cold)
    lane1 = jax.tree_util.tree_map(lambda a: a[1], sh.state)
    assert int(cold_store.cold_occupancy(lane1.cold)) == 0
    assert int(lane1.hot.count.sum()) == 0
    assert ref.stats.spilled == sh.stats.spilled > 0
    assert ref.stats.promoted == sh.stats.promoted > 0


# ---------------------------------------------------------------------------
# Compacted walks: one serial trip per acting record, exact on crafted edges
# ---------------------------------------------------------------------------

WALK_F, WALK_C = 16, 8  # hot and cold sizes of the crafted walk cases
_WALK_H = np.arange(1, 1 << 17, dtype=np.int64)


@functools.cache
def _walk_slots():
    """Hot slot and both cold candidates of every hash in ``_WALK_H``."""
    h = jnp.asarray(_WALK_H, jnp.int32)
    a, b = cold_store.cold_slots(h, WALK_C)
    return (np.asarray(ft.hash_slot(h, WALK_F)), np.asarray(a),
            np.asarray(b))


def _pick(taken, *, hot=None, a=None, pair=None):
    """The first unused hash with the given hot slot, first cold candidate
    and/or unordered cold candidate pair."""
    hs, ca, cb = _walk_slots()
    ok = ~np.isin(_WALK_H, list(taken))
    if hot is not None:
        ok &= hs == hot
    if a is not None:
        ok &= ca == a
    if pair is not None:
        ok &= ((ca == pair[0]) & (cb == pair[1])) | (
            (ca == pair[1]) & (cb == pair[0]))
    h = int(_WALK_H[np.flatnonzero(ok)[0]])
    taken.add(h)
    return h


def _walk_batch(hashes, t0):
    """Packets with sizes and payload that differ per flow and packet, so
    every wide leaf tells one record from another."""
    h = np.asarray(hashes, np.int64)
    ts = t0 + np.arange(len(hashes))
    b = make_batch(hashes, ts.tolist(), (40 + (h * 7 + ts) % 1400).tolist())
    pay = (h[:, None] * 3 + ts[:, None] + np.arange(16)[None]) % 256
    return b._replace(payload=jnp.asarray(pay, jnp.int32))


def _walk_idle():
    taken: set = set()
    f = [_pick(taken, hot=s) for s in (1, 4, 9, 13)]
    return [(_walk_batch(f + f, 1), (0, 0, 0)),
            (_walk_batch(f[::-1] + f, 10), (0, 0, 0))]


def _walk_all_spill():
    """Every packet of the second batch evicts its slot's occupant."""
    taken: set = set()
    c1, a1, a2 = (_pick(taken, hot=3) for _ in range(3))
    c2, b1, b2 = (_pick(taken, hot=11) for _ in range(3))
    return [(_walk_batch([c1, c2] * 4, 1), (0, 0, 0)),
            (_walk_batch([a1, b1, a2, b2] * 2, 10), (8, 0, 8))]


def _walk_spill_evicts_spill():
    """Y's spill evicts X's, spilled earlier in the same batch: both land on
    cold slot p, Y last, so Y wins in every leaf."""
    taken: set = set()
    p, q = 2, 5
    x = _pick(taken, hot=1, a=p, pair=(p, q))
    y = _pick(taken, hot=6, pair=(p, q))
    w = _pick(taken, hot=12, a=q)
    x2, y2, w2 = (_pick(taken, hot=s) for s in (1, 6, 12))
    return [(_walk_batch([x, y, w, x, y, y, x, w], 1), (0, 0, 0)),
            # W spills with stamp 100 > X's 7
            (_walk_batch([w] + [w2] * 7, 100), (1, 0, 1)),
            (_walk_batch([x2, y2] * 4, 200), (2, 0, 2))]


def _walk_promote_shrinks():
    """Two pre-pass candidates, A (hot slot 2) then B (hot slot 9): A's
    displaced occupant O evicts B's cold source (B's stamp 5 < R's 8), so
    the second trip finds nothing to promote."""
    taken: set = set()
    cb, r, ca = 1, 6, 3
    o = _pick(taken, hot=2, pair=(cb, r))
    a = _pick(taken, hot=2, a=ca)
    b = _pick(taken, hot=9, a=cb)
    b2 = _pick(taken, hot=9)
    rr = _pick(taken, hot=14, a=r)
    r2 = _pick(taken, hot=14)
    return [(_walk_batch([a, b, rr, a, b, rr, rr, rr], 1), (0, 0, 0)),
            (_walk_batch([o, b2, r2] * 2 + [o, b2], 100), (3, 0, 3)),
            # walk 2 candidates + 1 spill (B evicts B2); promoted only A
            (_walk_batch([a, b] * 4, 200), (1, 1, 3))]


def _walk_lanes():
    """Two vmapped lanes: lane 0 a collision storm, lane 1 almost idle."""
    S = 2
    rng = np.random.default_rng(3)
    hs, _, _ = _walk_slots()
    lane = shard_of(_WALK_H, S)
    storm = [int(h) for s in (2, 7, 13)
             for h in _WALK_H[(lane == 0) & (hs == s)][:4]]
    calm = [int(h) for s in (4, 10)
            for h in _WALK_H[(lane == 1) & (hs == s)][:2]]
    batches = []
    for k in range(8):
        hashes = np.concatenate([rng.choice(storm, 13),
                                 rng.choice(calm, 3)])
        batches.append((_walk_batch(hashes.tolist(), 1 + 20 * k), None))
    return batches


WALK_CASES = {"idle": _walk_idle, "all_spill": _walk_all_spill,
              "spill_evicts_spill": _walk_spill_evicts_spill,
              "promote_shrinks": _walk_promote_shrinks,
              "lanes": _walk_lanes}


def _walk_candidates(oracle, batch_dicts):
    """Pre-pass promote candidates: segment heads whose tuple is not live in
    hot and is in cold."""
    heads: dict = {}
    for pkt in batch_dicts:
        heads.setdefault(oracle.slot_of(pkt["tuple_hash"]), pkt["tuple_hash"])
    return sum(oracle._cold_find(h) is not None
               for s, h in heads.items()
               if oracle.slots.get(s, {}).get("tuple_id") != h)


def _assert_every_leaf_equal(state, oracle):
    """:func:`assert_two_level_state_equal` plus last_ts and every wide
    leaf of both tiers."""
    assert_two_level_state_equal(state, oracle)
    for tier, table in (("hot", oracle.slots), ("cold", oracle.cold)):
        leaves = getattr(state, tier)
        for s, e in table.items():
            assert int(leaves.last_ts[s]) == e["last_ts"], (tier, s)
            for k in ("series", "sizes", "payload"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(leaves, k)[s]),
                    np.asarray(e[k], np.int32), err_msg=f"{tier}[{s}].{k}")


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_compacted_walks_match_oracle(params, case):
    """The promote and spill walks make one trip per acting record
    (``cold_walk`` = pre-pass promote candidates + spill records) and stay
    bit-exact to the sequential oracle: no acting record, all P records
    acting, a spill evicting an earlier spill of its batch, a candidate
    losing its cold source mid-walk, and vmapped lanes whose trip counts
    differ."""
    steps = WALK_CASES[case]()
    S = 2 if case == "lanes" else 1
    P = steps[0][0].ts.shape[0]
    cfg = PipelineConfig(batch_size=P, max_ready=2 * S,
                         flow_model="transformer", table_size=WALK_F,
                         top_n=16, top_k=15, pay_bytes=16, cold_size=WALK_C)
    if S == 1:
        pipe = OctopusPipeline(params["mlp"], params["transformer"], cfg)
    else:
        pipe = ShardedOctopusPipeline(params["mlp"], params["transformer"],
                                      cfg, num_shards=S, backend="vmap")
    oracles = [TwoLevelOracle(WALK_F, WALK_C, top_n=16, top_k=15,
                              pay_bytes=16) for _ in range(S)]
    lane_walks = []
    for batch, want in steps:
        pkts = batch_as_dicts(batch)
        got = np.zeros(3, int)  # spilled, promoted, walked
        walks = []
        for k, orc in enumerate(oracles):
            mine = [d for d in pkts if shard_of(d["tuple_hash"], S) == k]
            s0, p0 = orc.spilled, orc.promoted
            cands = _walk_candidates(orc, mine)
            orc.step_batch(mine, cfg.max_ready // S)
            d = (orc.spilled - s0, orc.promoted - p0)
            walks.append(cands + d[0])
            got += (d[0], d[1], cands + d[0])
        lane_walks.append(walks)
        out = pipe.step(batch)
        assert (int(out.spilled), int(out.promoted),
                int(out.cold_walk)) == tuple(got)
        if want is not None:
            assert tuple(got) == want
    for k, orc in enumerate(oracles):
        lane = pipe.state if S == 1 else jax.tree_util.tree_map(
            lambda a: a[k], pipe.state)
        _assert_every_leaf_equal(lane, orc)
    assert pipe.stats.cold_walk == sum(map(sum, lane_walks))
    if case == "lanes":
        assert any(w[0] != w[1] for w in lane_walks)
        assert all(w[0] > 0 for w in lane_walks[1:])
