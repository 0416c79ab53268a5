"""The benchmark's vectorised traffic generator: determinism by seed, flows
that keep their timestamp order and stay on one port, a constant live
population, and the size and popularity distributions the mix files ask
for."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.traffic import generator  # noqa: E402

BIG_SEED = 2**31 + 12345  # the driver's seeds exceed 32 signed bits


def streams(name="churn.sat", flows=4096, seed=BIG_SEED, lanes=1):
    return generator.Streams(generator.load_mix(name), lanes=lanes,
                             flows_per_lane=flows, seed=seed, pay_bytes=16)


def blocks(s, k=6, n=256):
    return [s.block(n) for _ in range(k)]


def cat(bs, field):
    return np.concatenate([getattr(b, field) for b in bs], axis=1)


@pytest.mark.parametrize("name", ["churn.sat", "mice.sat", "churn.p99"])
def test_same_seed_same_packets(name):
    a, b = blocks(streams(name)), blocks(streams(name))
    for f in generator.Block.FIELDS:
        np.testing.assert_array_equal(cat(a, f), cat(b, f))
    c = blocks(streams(name, seed=BIG_SEED + 1))
    assert not np.array_equal(cat(a, "tuple_hash"), cat(c, "tuple_hash"))


def test_flows_keep_order_and_their_port():
    s = streams()
    bs = blocks(s, k=8)
    th, ts, seq, serial = (cat(bs, f) for f in ("tuple_hash", "ts", "seq", "flow_serial"))
    assert (np.diff(ts, axis=1) > 0).all()  # each port's clock rises
    port_of = {}
    last = {}
    for p in range(s.ports):
        for h, q, f in zip(th[p], seq[p], serial[p]):
            assert port_of.setdefault(int(h), p) == p  # one port per flow
            assert last.get(int(f), -1) + 1 == q  # consecutive packets
            last[int(f)] = int(q)
    # a tuple hash names one flow
    assert len({(int(h), int(f)) for h, f in zip(th.ravel(), serial.ravel())}) == len(last)
    assert (th > 0).all()


def test_population_stays_constant_while_flows_churn():
    s = streams(flows=1024)
    live0 = set(s.flow.tolist())
    blocks(s, k=10)
    assert len(set(s.flow.tolist())) == 1024 == s.ports * s.slots
    assert len(live0 - set(s.flow.tolist())) > 0  # flows ended and were replaced
    assert (s.sent < s.length).all()


def test_flow_sizes_follow_the_mix():
    mix = generator.load_mix("churn.sat")
    q = generator.size_quantiles(mix["flow_size"])
    pts = np.asarray(mix["flow_size"]["points"])
    top = mix["flow_size"]["max_packets"]
    for size, p in pts[2:]:
        # the stratified table puts the CDF's share at or below each point;
        # what lies beyond the truncation sits at the maximum
        if size < top:
            assert abs((q <= size).mean() - p) < 0.01
    assert q.max() == top and (q == top).mean() > 0.25
    mice = generator.size_quantiles(generator.load_mix("mice.sat")["flow_size"])
    assert mice.min() == 15 and mice.max() == 24
    assert np.allclose(np.bincount(mice)[15:], len(mice) / 10, rtol=0.01)


def test_every_seed_gets_the_same_sizes():
    a, b = streams(seed=1), streams(seed=BIG_SEED)
    assert sorted(a._size_perm) == sorted(b._size_perm)
    assert not np.array_equal(a._size_perm, b._size_perm)


def test_packet_sizes_and_popularity():
    s = streams()
    bs = blocks(s, k=10)
    size = cat(bs, "size").ravel()
    share = {v: (size == v).mean() for v in (64, 594, 1518)}
    assert set(np.unique(size)) == {64, 594, 1518}
    for v, w in zip((64, 594, 1518), (7, 4, 1)):
        assert abs(share[v] - w / 12) < 0.01
    # Zipf(1): the most popular slot of a port gets about 1/H(slots) of its picks
    cdf = generator.zipf_cdf(s.slots, 1.0)
    assert abs(cdf[0] - 1 / np.sum(1 / np.arange(1, s.slots + 1))) < 1e-12


def test_lanes_scale_ports_and_flows():
    s = streams(lanes=4, flows=1024)
    assert s.ports == 64 and s.ports * s.slots == 4096


def test_open_loop_arrivals():
    due, port = generator.arrivals(1000, 10.0, 16, BIG_SEED)
    assert len(due) == 1000 and (np.diff(due) >= 0).all()
    assert 0 <= due.min() and due.max() < 10.0
    assert set(np.unique(port)) <= set(range(16))
    d2, p2 = generator.arrivals(1000, 10.0, 16, BIG_SEED)
    np.testing.assert_array_equal(due, d2)
    np.testing.assert_array_equal(port, p2)
