"""Plain flow trackers: the Octopus flow table (paper section 3.1) in dicts and
ints, one packet at a time, with no JAX.

``OracleTracker`` is the hot table: establish on a new tuple (evicting any
stale flow in the slot), update the history word and the series, size and
payload memories, and emit flows whose packet count reached ``top_n``,
lowest slot first, up to ``max_ready`` per step.  ``TwoLevelOracle`` adds
the cold tier behind it: per step, promote -> merge with spill capture ->
sequential cold inserts (2-choice hashing, age-stamped victims) -> scrub ->
drain.  Both follow the documented step semantics of the system under test
one-for-one; the hashes are written out here so that nothing is imported
from it.  An entry dict, once evicted, is never written again, so records
move between the tiers without copies.
"""
from __future__ import annotations

INT_MAX = 2**31 - 1
_M32 = 0xFFFFFFFF


def hot_slot(tuple_hash: int, table_size: int) -> int:
    """Hot-table slot: multiplicative hash (golden-ratio constant), xor-fold."""
    h = ((tuple_hash & _M32) * 0x9E3779B1) & _M32
    h ^= h >> 16
    return h % table_size


def cold_slots(tuple_hash: int, cold_size: int) -> tuple[int, int]:
    """The tuple's two cold candidate slots (murmur3 finalizer constants)."""
    a = ((tuple_hash & _M32) * 0x85EBCA6B) & _M32
    a ^= a >> 13
    b = ((tuple_hash & _M32) * 0xC2B2AE35) & _M32
    b ^= b >> 16
    return a % cold_size, b % cold_size


class OracleTracker:
    def __init__(self, table_size: int, top_n: int, top_k: int, pay_bytes: int):
        self.table_size = table_size
        self.top_n = top_n
        self.top_k = top_k
        self.pay_bytes = pay_bytes
        self.slots: dict[int, dict] = {}
        self.ready: set[int] = set()  # slots whose count reached top_n
        self._zero_row = [0] * pay_bytes  # shared: payload rows are replaced, never written

    def slot_of(self, tuple_hash: int) -> int:
        return hot_slot(tuple_hash, self.table_size)

    def _fresh(self, tuple_hash: int) -> dict:
        return {
            "tuple_id": tuple_hash, "count": 0, "last_ts": 0,
            "flow_dur": 0, "flow_size": 0, "max_size": 0, "min_size": INT_MAX,
            "max_intv": 0, "min_intv": INT_MAX, "size_fwd": 0, "size_bwd": 0,
            "flags_acc": 0, "last_size": 0, "payload_bytes": 0, "proto": 0,
            "series": [0] * self.top_n, "sizes": [0] * self.top_n,
            "payload": [self._zero_row] * self.top_k,
        }

    def process(self, pkt: dict) -> None:
        slot = self.slot_of(pkt["tuple_hash"])
        e = self.slots.get(slot)
        if e is None or e["count"] == 0 or e["tuple_id"] != pkt["tuple_hash"]:
            e = self._fresh(pkt["tuple_hash"])  # establish (evicts any stale flow)
            self.slots[slot] = e
            self.ready.discard(slot)
        intv = pkt["ts"] - e["last_ts"] if e["count"] > 0 else 0
        size = pkt["size"]
        c0 = e["count"]
        e["flow_dur"] += intv
        e["flow_size"] += size
        e["max_size"] = max(e["max_size"], size)
        e["min_size"] = min(e["min_size"], size)
        e["max_intv"] = max(e["max_intv"], intv)
        e["min_intv"] = min(e["min_intv"], intv)
        e["last_ts"] = pkt["ts"]
        e["size_fwd"] += size if pkt["dir"] == 0 else 0
        e["size_bwd"] += size if pkt["dir"] == 1 else 0
        e["flags_acc"] += pkt["flags"]
        e["last_size"] = size
        e["payload_bytes"] += min(size, self.pay_bytes)
        e["proto"] = pkt["proto"]
        if c0 < self.top_n:
            e["series"][c0] = intv
            e["sizes"][c0] = size
        if c0 < self.top_k:
            e["payload"][c0] = list(pkt["payload"])
        e["count"] = c0 + 1
        if c0 + 1 >= self.top_n:
            self.ready.add(slot)

    def feature_word(self, e: dict) -> list:
        return [e["flow_dur"], e["count"], e["flow_size"], e["max_size"],
                e["min_size"], e["max_intv"], e["min_intv"], e["last_ts"],
                e["size_fwd"], e["size_bwd"], e["flags_acc"], e["last_size"],
                e["payload_bytes"], e["proto"], 0, 0]

    def drain_ready(self, max_ready: int) -> list:
        ready = sorted(self.ready)[:max_ready]
        emitted = []
        for s in ready:
            e = self.slots.pop(s)
            self.ready.discard(s)
            emitted.append({"slot": s, "tuple_id": e["tuple_id"],
                            "count": e["count"],
                            "features": self.feature_word(e),
                            "series": e["series"], "sizes": e["sizes"],
                            "payload": e["payload"]})
        return emitted

    def step_batch(self, batch_dicts: list, max_ready: int) -> list:
        """One pipeline step: merge the packets in order, then drain."""
        for pkt in batch_dicts:
            self.process(pkt)
        return self.drain_ready(max_ready)


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
        a, b = cold_slots(h, self.cold_size)
        if a in self.cold and self.cold[a]["tuple_id"] == h:
            return a
        if b in self.cold and self.cold[b]["tuple_id"] == h:
            return b
        return None

    def _cold_insert(self, entry):
        """Own entry -> first empty candidate -> smaller stamp (tie prefers
        candidate a)."""
        h = entry["tuple_id"]
        a, b = cold_slots(h, self.cold_size)
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
            if entry["count"] >= self.top_n:
                self.ready.add(s)
            else:
                self.ready.discard(s)
            self.promoted += 1
        # 2. merge with spill capture, in packet order
        spills = []
        for pkt in batch_dicts:
            s = self.slot_of(pkt["tuple_hash"])
            e = self.slots.get(s)
            if e is not None and e["tuple_id"] != pkt["tuple_hash"]:
                spills.append(e)  # process() re-establishes the slot anew
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
