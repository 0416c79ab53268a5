"""One device->host read per served dispatch: ``step_masked`` reads the host
pack back in one transfer and hands back numpy fields in their own dtypes.
What it serves is what the step computed: drained flows and counters against
the oracle trackers, verdicts and the rule table against the step program's
own outputs read field by field, on a twin pipeline fed the same batches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_cold_store import TwoLevelOracle
from test_pipeline import OracleTracker, batch_as_dicts

from repro.core import decisions
from repro.data.traffic import TrafficConfig, TrafficGenerator, partition_batch, shard_of
from repro.models import paper_models
from repro.serving import OctopusPipeline, PipelineConfig, ShardedOctopusPipeline
from repro.serving import pipeline as pipeline_mod

BUCKETS = (16, 32)
DISPATCHES = 10
# each pipeline, as (config overrides, lanes, traffic overrides); the cold
# tier runs a non-default flow head, so its verdicts take both actions
KINDS = {
    "hot": (dict(table_size=64), 0, dict(active_flows=24, collision_free=False)),
    "cold": (dict(table_size=16, cold_size=64,
                  flow_head=decisions.AnomalyHead(deny_threshold=0.2)),
             0, dict(active_flows=48, collision_free=False)),
    "sharded": (dict(table_size=64), 2, dict(active_flows=24, collision_free=False)),
}
FIELDS = ("pkt_actions", "flow_actions", "flow_cls", "new_flows", "evicted",
          "spilled", "promoted", "cold_walk", "fallback_slots", "ready_left")


class Counting:
    """Oracle mixin: the merge's establish and evict counts, as the step's
    ``new_flows`` and ``evicted`` define them."""

    new_flows = evicted = 0

    def process(self, pkt: dict) -> None:
        e = self.slots.get(self.slot_of(pkt["tuple_hash"]))
        if e is None or e["count"] == 0 or e["tuple_id"] != pkt["tuple_hash"]:
            self.new_flows += 1
            self.evicted += e is not None and e["count"] > 0
        super().process(pkt)


class HotOracle(Counting, OracleTracker):
    def step_batch(self, pkts, max_ready):
        for p in pkts:
            self.process(p)
        return self.drain_ready(max_ready)


class ColdOracle(Counting, TwoLevelOracle):
    pass


class LanesOracle:
    """One :class:`HotOracle` per lane, fed the lane's packets by
    ``shard_of``, each draining its split of the budget; the drained rows in
    lane order, as the lanes' merge concatenates them."""

    def __init__(self, lanes: int, *args, **kw):
        self.lanes = [HotOracle(*args, **kw) for _ in range(lanes)]

    def step_batch(self, pkts, max_ready):
        n = len(self.lanes)
        lane = shard_of(np.asarray([p["tuple_hash"] for p in pkts], np.int32), n)
        return [e for i, o in enumerate(self.lanes)
                for e in o.step_batch([p for p, j in zip(pkts, lane) if j == i],
                                      max_ready // n)]

    def __getattr__(self, name):  # the counts, summed over lanes
        return sum(getattr(o, name) for o in self.lanes)


@pytest.fixture(scope="module")
def params():
    return (paper_models.init_paper_model("mlp", jax.random.PRNGKey(0)),
            paper_models.init_paper_model("transformer", jax.random.PRNGKey(2)))


def make(params, kind, lane_batch=None, **kw):
    over, lanes, _ = KINDS[kind]
    cfg = PipelineConfig(**{**dict(batch_size=max(BUCKETS), max_ready=8,
                                   flow_model="transformer", top_n=4, top_k=15,
                                   pay_bytes=16), **over, **kw})
    if lanes:
        return ShardedOctopusPipeline(*params, cfg, num_shards=lanes,
                                      lane_batch=lane_batch)
    return OctopusPipeline(*params, cfg)


def oracle_for(pipe):
    c = pipe.cfg
    if isinstance(pipe, ShardedOctopusPipeline):
        return LanesOracle(pipe.num_shards, c.table_size, top_n=c.top_n,
                           top_k=c.top_k, pay_bytes=c.pay_bytes)
    if c.cold_size:
        return ColdOracle(c.table_size, c.cold_size, top_n=c.top_n,
                          top_k=c.top_k, pay_bytes=c.pay_bytes)
    return HotOracle(c.table_size, top_n=c.top_n, top_k=c.top_k,
                     pay_bytes=c.pay_bytes)


def traffic(kind, bucket, seed=7):
    return TrafficGenerator(TrafficConfig(
        batch_size=bucket, elephant_fraction=0.5, table_size=KINDS[kind][0]["table_size"],
        seed=seed, burst_prob=0.3, **KINDS[kind][2]))


def raw_step(twin, packets, keep):
    """The twin's step program alone: its outputs, still on the device."""
    if isinstance(twin, ShardedOctopusPipeline):
        sb = partition_batch(packets, twin.num_shards, keep=keep)[0]
        twin.state, out = twin._masked_fn(twin.state, sb.shards, sb.keep, sb.src)
    else:
        twin.state, out = twin._masked_fn(twin.state, packets, jnp.asarray(keep))
    return out


def host_fields(out) -> dict:
    return {**{k: getattr(out, k) for k in FIELDS},
            "mask": out.drained.mask, "tuple_id": out.drained.tuple_id}


@pytest.fixture
def device_gets(monkeypatch):
    """Every ``jax.device_get`` the pipeline makes, counted."""
    calls = []
    get = jax.device_get

    def counted(x):
        calls.append(len(jax.tree_util.tree_leaves(x)))
        return get(x)

    monkeypatch.setattr(pipeline_mod.jax, "device_get", counted)
    return calls


@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize("kind", list(KINDS))
def test_step_masked_reads_once_and_serves_what_the_step_computed(
        params, kind, bucket, device_gets):
    pipe, twin = make(params, kind), make(params, kind)
    pipe.warm_bucket(bucket)
    compiled = pipeline_mod._flatten._cache_size()
    oracle, ref_rules = oracle_for(pipe), decisions.RuleTable()
    gen, flows = traffic(kind, bucket), 0
    for i in range(DISPATCHES):
        batch = gen.next_batch()
        keep = np.arange(bucket) < bucket - i % 4
        packets = jax.tree_util.tree_map(jnp.asarray, batch)  # as the service
        before = len(device_gets)
        out = pipe.step_masked(packets, keep)
        assert device_gets[before:] == [1]  # one call, one array: the pack
        ref = raw_step(twin, packets, keep)

        # host fields: numpy in the step's own dtypes, equal to the step's
        # outputs read one by one; the flow scores stay on the device
        got, want = host_fields(out), host_fields(ref)
        for k, g in got.items():
            w = np.asarray(want[k])
            assert isinstance(g, np.ndarray) and g.dtype == w.dtype, k
            np.testing.assert_array_equal(g, w, err_msg=k)
        assert got["mask"].dtype == np.bool_
        assert isinstance(out.flow_scores, jax.Array)
        np.testing.assert_array_equal(np.asarray(out.flow_scores),
                                      np.asarray(ref.flow_scores))

        # the rule table, fed from the reference outputs
        m = np.asarray(ref.drained.mask)
        ref_rules.update(np.asarray(batch.tuple_hash)[keep],
                         np.asarray(ref.pkt_actions)[keep])
        if m.any():
            ref_rules.update(np.asarray(ref.drained.tuple_id)[m],
                             np.asarray(ref.flow_actions)[m],
                             np.asarray(ref.flow_cls)[m])
        assert pipe.rules.rules == ref_rules.rules

        # drained flows against the oracle tracker
        kept = [p for p, k in zip(batch_as_dicts(batch), keep) if k]
        expect = [e["tuple_id"] for e in oracle.step_batch(kept, pipe.cfg.max_ready)]
        assert got["tuple_id"][got["mask"]].tolist() == expect
        flows += len(expect)

    s = pipe.stats
    assert s.host_reads == s.dispatches == DISPATCHES
    assert (s.flows, s.new_flows, s.evicted) == (flows, oracle.new_flows, oracle.evicted)
    assert flows > 0 and s.new_flows > 0
    if pipe.cfg.cold_size:
        assert (s.spilled, s.promoted) == (oracle.spilled, oracle.promoted)
        assert s.spilled > 0 and s.promoted > 0
        assert len({r["action"] for r in pipe.rules.rules.values()}) > 1
    # the pack compiled with the bucket, never in the dispatch
    assert pipeline_mod._flatten._cache_size() == compiled


@pytest.mark.parametrize("path", ["step", "step_many", "overlap"])
def test_step_paths_read_once_and_match_the_served_step(params, path,
                                                        device_gets):
    """``step``, ``step_many`` and the overlapped ``run`` go through the same
    read-back: one per dispatch, the rule table as ``step_masked`` builds it
    from the same batches."""
    scan = 2 if path == "step_many" else 1
    pipe = make(params, "hot", batch_size=16, scan_len=scan,
                overlap=path == "overlap")
    served = make(params, "hot", batch_size=16)
    pipe.warmup()
    served.warm_bucket(16)
    gen = traffic("hot", 16, seed=9)
    batches = [gen.next_batch() for _ in range(6)]
    before = len(device_gets)
    if path == "step_many":
        outs = [pipe.step_many(batches[i:i + 2]) for i in range(0, 6, 2)]
    elif path == "step":
        outs = [pipe.step(b) for b in batches]
    else:
        pipe.run(iter(batches))
        outs = []
    assert len(device_gets) - before == pipe.stats.dispatches == pipe.stats.host_reads
    assert pipe.stats.dispatches == 6 // scan
    for out in outs:
        assert isinstance(out.pkt_actions, np.ndarray)
        assert out.drained.mask.dtype == np.bool_
    for b in batches:
        served.step_masked(b, np.ones(16, bool))
    assert pipe.rules.rules == served.rules.rules
    assert pipe.stats.flows == served.stats.flows > 0


def test_sharded_overflow_rounds_read_in_the_same_call(params, device_gets):
    """A step whose lanes overflow runs several device rounds; their
    counters and verdicts come back in the pack's one call, and the step
    serves what the single-lane pipeline serves."""
    lanes = make(params, "sharded", batch_size=16, lane_batch=4)
    one = make(params, "hot", batch_size=16)
    lanes.warmup()
    # no cross-lane collisions and no backlog: the lanes then serve exactly
    # what one table serves
    gen = TrafficGenerator(TrafficConfig(batch_size=16, active_flows=12,
                                         table_size=64, seed=3))
    for _ in range(4):
        batch = gen.next_batch()
        before = len(device_gets)
        out = lanes.step(batch)
        assert len(device_gets) - before == 1
        ref = one.step(batch)
        np.testing.assert_array_equal(out.pkt_actions, ref.pkt_actions)
        assert isinstance(out.new_flows, np.ndarray) and out.new_flows.shape == ()
    s = lanes.stats
    assert s.host_reads == 4 < s.dispatches
    assert (s.new_flows, s.flows) == (one.stats.new_flows, one.stats.flows)
    assert lanes.rules.rules == one.rules.rules
