"""Vectorised, seeded packet streams for the benchmark's traffic mixes.

One general generator reads a mix file (``bench/traffic/<name>.json``) and
makes every port's packet stream.  Each port holds a fixed population of
live flow slots; a slot always carries one live flow, and when that flow has
sent its last packet a fresh flow (new tuple hash, new size) takes the slot,
so the live population stays constant while flows churn.  Packets are picked
per port by slot popularity (Zipf over the slot's rank), so every flow
belongs to exactly one port and its packets appear in port order with rising
timestamps.

All flow state lives in numpy arrays over (ports * slots); one call makes a
block of packets for every port at once, so generation never sets the pace
of the measured window.

Every seed gets the same multiset of flow sizes (stratified quantiles of the
size distribution, permuted by the seed) and the same popularity weights, so
seeds reorder the work rather than change it.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

MIX_DIR = Path(__file__).resolve().parent
# flows drawn per pass over the stratified size table
SIZE_TABLE = 1 << 16
# tuple hashes are a bijection of a flow serial on 31 bits: unique, positive
_HASH_MUL = 0x2545F491  # odd, so multiplication mod 2**31 is invertible
_HASH_MASK = (1 << 31) - 1


def load_mix(name: str, mix_dir: Path = MIX_DIR) -> dict:
    """The mix file ``<mix_dir>/<name>.json`` as a dict."""
    path = Path(mix_dir) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def size_quantiles(spec: dict, n: int = SIZE_TABLE) -> np.ndarray:
    """``n`` flow sizes in packets at the stratified quantiles (i + 0.5) / n
    of the mix's size distribution (ascending)."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["kind"]
    if kind == "cdf":
        pts = np.asarray(spec["points"], np.float64)  # (size, cum. prob.)
        sizes = np.interp(u, pts[:, 1], pts[:, 0])
        sizes = np.minimum(np.ceil(sizes), spec["max_packets"])
    elif kind == "uniform":
        lo, hi = spec["min_packets"], spec["max_packets"]
        sizes = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown flow-size kind {kind!r}")
    return np.maximum(sizes, 1).astype(np.int64)


def zipf_cdf(slots: int, s: float) -> np.ndarray:
    """Cumulative pick probability of slot ranks 1..slots under Zipf(s)."""
    w = 1.0 / np.arange(1, slots + 1, dtype=np.float64) ** s
    c = np.cumsum(w)
    return c / c[-1]


@dataclass
class Block:
    """Packets of one block: every leaf is (ports, n) (payload (ports, n, B)),
    in each port's arrival order."""

    ts: np.ndarray
    size: np.ndarray
    dir: np.ndarray
    flags: np.ndarray
    proto: np.ndarray
    tuple_hash: np.ndarray
    payload: np.ndarray
    flow_serial: np.ndarray  # (ports, n) which flow each packet belongs to
    seq: np.ndarray  # (ports, n) packet index within its flow

    FIELDS = ("ts", "size", "dir", "flags", "proto", "tuple_hash", "payload")


class Streams:
    """Packet streams of every port of one cell.

    ``ports`` (the mix's per lane) and ``flows_per_lane`` (the
    configuration's live population) are multiplied by ``lanes``; flows
    split evenly over ports.  ``block(n)`` returns the next ``n`` packets of
    every port."""

    def __init__(self, mix: dict, *, lanes: int, flows_per_lane: int, seed: int,
                 pay_bytes: int):
        self.mix = mix
        self.ports = int(mix["ports_per_lane"]) * lanes
        flows = int(flows_per_lane) * lanes
        if flows % self.ports:
            raise ValueError(f"{flows} flows do not split over {self.ports} ports")
        self.slots = flows // self.ports
        self.pay_bytes = pay_bytes
        ss = np.random.SeedSequence(int(seed))
        self.rng = np.random.default_rng(ss)
        self._cdf = zipf_cdf(self.slots, float(mix["popularity"]["s"]))
        self._sizes = size_quantiles(mix["flow_size"])
        self._size_perm = self.rng.permutation(self._sizes)
        self._size_next = 0
        pk = mix["packet_size"]
        self._pkt_sizes = np.asarray(pk["sizes"], np.int32)
        w = np.asarray(pk["weights"], np.float64)
        self._pkt_cdf = np.cumsum(w) / w.sum()
        self._hash_base = int(self.rng.integers(0, 1 << 31))
        self._serial = 0
        total = self.ports * self.slots
        self.flow = np.empty(total, np.int64)  # serial of the slot's flow
        self.length = np.empty(total, np.int64)  # its size in packets
        self.sent = np.zeros(total, np.int64)  # packets it has sent
        self._spawn(np.arange(total))
        # each port's clock (us); the first packets start at a random phase
        self.clock = self.rng.integers(0, 1000, self.ports).astype(np.int64)

    # ---------------------------------------------------------------- flows
    def _take_sizes(self, n: int) -> np.ndarray:
        out = np.empty(n, np.int64)
        got = 0
        while got < n:
            k = min(n - got, len(self._size_perm) - self._size_next)
            out[got:got + k] = self._size_perm[self._size_next:self._size_next + k]
            self._size_next += k
            got += k
            if self._size_next == len(self._size_perm):
                self._size_perm = self.rng.permutation(self._sizes)
                self._size_next = 0
        return out

    def _spawn(self, slots: np.ndarray) -> None:
        """Fresh flows (new serials and sizes) in ``slots``."""
        n = len(slots)
        self.flow[slots] = self._serial + np.arange(n)
        self._serial += n
        self.length[slots] = self._take_sizes(n)
        self.sent[slots] = 0

    def tuple_hash(self, serial: np.ndarray) -> np.ndarray:
        """Tuple hash of flow ``serial``: unique over 2**31 serials, positive."""
        s = (np.asarray(serial, np.int64) + self._hash_base) & _HASH_MASK
        return ((s * _HASH_MUL) & _HASH_MASK).astype(np.int32)

    # -------------------------------------------------------------- packets
    def block(self, n: int) -> Block:
        """The next ``n`` packets of every port."""
        P, L = self.ports, self.slots
        rank = np.searchsorted(self._cdf, self.rng.random((P, n)), side="right")
        rank = np.minimum(rank, L - 1)
        g = (np.arange(P)[:, None] * L + rank).reshape(-1)  # global slot
        # ordinal of each pick among the picks of its slot, in stream order
        order = np.argsort(g, kind="stable")
        gs = g[order]
        first = np.r_[0, np.flatnonzero(gs[1:] != gs[:-1]) + 1]
        run = np.repeat(first, np.diff(np.r_[first, len(gs)]))
        c = np.empty_like(g)
        c[order] = np.arange(len(gs)) - run
        pos = self.sent[g] + c  # position in the chain of flows of the slot
        serial = np.empty_like(g)
        seq = np.empty_like(g)
        active = np.arange(len(g))
        while active.size:
            s = g[active]
            inside = pos[active] < self.length[s]
            serial[active[inside]] = self.flow[s[inside]]
            seq[active[inside]] = pos[active[inside]]
            over = active[~inside]
            if not over.size:
                break
            # the slot's current flow is used up: later picks go to a new one
            pos[over] -= self.length[g[over]]
            self._spawn(np.unique(g[over]))
            active = over
        # packets sent by each slot's (final) current flow
        last = np.zeros(P * L, np.int64) - 1
        mine = self.flow[g] == serial
        np.maximum.at(last, g[mine], seq[mine])
        touched = last >= 0
        self.sent[touched] = last[touched] + 1
        done = np.flatnonzero(self.sent >= self.length)
        if done.size:
            self._spawn(done)

        serial = serial.reshape(P, n)
        seq = seq.reshape(P, n)
        steps = self.rng.integers(1, 4, (P, n))
        ts = self.clock[:, None] + np.cumsum(steps, axis=1)
        self.clock = ts[:, -1].copy()
        size = self._pkt_sizes[np.searchsorted(self._pkt_cdf,
                                               self.rng.random((P, n)),
                                               side="right")]
        th = self.tuple_hash(serial)
        return Block(
            ts=ts.astype(np.int32), size=size.astype(np.int32),
            dir=self.rng.integers(0, 2, (P, n), dtype=np.int32),
            flags=self.rng.integers(0, 64, (P, n), dtype=np.int32),
            proto=np.where(th & 1, 17, 6).astype(np.int32),
            tuple_hash=th,
            payload=self.rng.integers(0, 256, (P, n, self.pay_bytes),
                                      dtype=np.int32),
            flow_serial=serial, seq=seq)


def arrivals(n: int, seconds: float, ports: int, seed: int) -> tuple:
    """Open-loop schedule: ``n`` request arrivals over ``seconds``, each at a
    uniform time (a Poisson process conditioned on its count), each to a
    uniform port.  Returns (due times ascending, port of each)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    due = np.sort(rng.random(n) * seconds)
    return due, rng.integers(0, ports, n)
