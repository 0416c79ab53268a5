"""Second-level (cold) flow table: the spill/promote half of the two-level
tracker (ROADMAP: hierarchical flow table — 10^5-10^6 flows, not 8k).

The hot level stays the per-lane :class:`~repro.core.flow_tracker.TrackerState`
bank, bit-identical to the single-level tracker (with ``cold_size == 0`` the
pipeline never touches this module).  This module adds a large
:class:`ColdState` table that collision evictions spill *into* (instead of
silently dropping the stale flow) and re-establishment promotes *from*:

  * **2-choice hashing** — every tuple hash owns two cold candidate slots
    (:func:`cold_slots`, two independent multiplicative mixers); an insert
    prefers a slot already holding the tuple (overwrite, never duplicate),
    then an empty slot (first candidate wins ties), and only then evicts the
    candidate with the smaller policy stamp.
  * **pluggable eviction policy** — ``"age"`` stamps entries with the
    spilled flow's ``last_ts`` (the longest-idle flow loses), ``"lru"`` with
    a monotonic insert tick (the least-recently-spilled flow loses).

Per-microbatch step semantics, applied by the serving pipelines and mirrored
one-for-one by the pure-Python oracle in ``tests/test_cold_store.py``:

  1. :func:`promote_pass` — for every batch-touched hot slot (ascending slot
     order) whose *head* packet's tuple is not live in hot but present in
     cold, the cold entry is loaded back into the hot slot before the merge
     (so the merge counts it as a hit and the flow's count keeps growing);
     a displaced hot occupant spills into cold first.
  2. the tracker merge runs on hot exactly as today, emitting
     :class:`~repro.core.flow_tracker.SpillRecords` for every eviction
     (``with_spills=True``; scan and segmented agree bit-exactly).
  3. :func:`apply_spills` — the records insert into cold sequentially in
     packet order (2-choice + policy).
  4. :func:`scrub_live` — any batch tuple live in hot after the merge is
     cleared from cold, so a tuple is never simultaneously live in hot and
     present in cold (a flow that re-established mid-batch after its own
     eviction leaves no stale twin behind).

The invariant from step 4 is what makes promotion sound: a cold lookup can
never resurrect an outdated copy of a flow the hot table still owns.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import flow_tracker as ft

COLD_POLICIES = ("age", "lru")


class ColdState(NamedTuple):
    """The cold table: one entry per slot, ``count == 0`` means empty.
    Leaves mirror :class:`~repro.core.flow_tracker.TrackerState` plus the
    eviction-policy ``stamp`` and the monotonic insert ``tick``."""

    tuple_id: jax.Array  # (C,) int32
    count: jax.Array  # (C,) int32 — 0 == empty
    last_ts: jax.Array  # (C,) int32
    features: jax.Array  # (C, 16) int32
    series: jax.Array  # (C, top_n) int32
    sizes: jax.Array  # (C, top_n) int32
    payload: jax.Array  # (C, top_k, pay_bytes) int32
    stamp: jax.Array  # (C,) int32 — eviction key (policy-defined)
    tick: jax.Array  # () int32 — total inserts so far (the lru clock)


class TwoLevelState(NamedTuple):
    """The hierarchical tracker state the pipelines carry when
    ``cold_size > 0``: the hot bank plus its cold spill table."""

    hot: ft.TrackerState
    cold: ColdState


def init_cold(cold_size: int, top_n: int, top_k: int,
              pay_bytes: int) -> ColdState:
    return ColdState(
        tuple_id=jnp.zeros((cold_size,), jnp.int32),
        count=jnp.zeros((cold_size,), jnp.int32),
        last_ts=jnp.zeros((cold_size,), jnp.int32),
        features=jnp.zeros((cold_size, 16), jnp.int32),
        series=jnp.zeros((cold_size, top_n), jnp.int32),
        sizes=jnp.zeros((cold_size, top_n), jnp.int32),
        payload=jnp.zeros((cold_size, top_k, pay_bytes), jnp.int32),
        stamp=jnp.zeros((cold_size,), jnp.int32),
        tick=jnp.int32(0),
    )


def init_two_level(table_size: int, cold_size: int, top_n: int, top_k: int,
                   pay_bytes: int) -> TwoLevelState:
    return TwoLevelState(
        hot=ft.init_state(table_size, top_n, top_k, pay_bytes),
        cold=init_cold(cold_size, top_n, top_k, pay_bytes))


def cold_slots(tuple_hash: jax.Array, cold_size: int) -> tuple[jax.Array,
                                                               jax.Array]:
    """The tuple's two cold candidate slots (2-choice hashing).  Two
    independent multiplicative mixers (murmur3 finalizer constants), both
    distinct from the hot table's :func:`~repro.core.flow_tracker.hash_slot`
    mixer so hot collisions don't correlate with cold collisions."""
    h = tuple_hash.astype(jnp.uint32)
    a = h * jnp.uint32(0x85EBCA6B)
    a = a ^ (a >> 13)
    b = h * jnp.uint32(0xC2B2AE35)
    b = b ^ (b >> 16)
    return ((a % jnp.uint32(cold_size)).astype(jnp.int32),
            (b % jnp.uint32(cold_size)).astype(jnp.int32))


def cold_slots_scalar(tuple_hash: int, cold_size: int) -> tuple[int, int]:
    """:func:`cold_slots` for one host-side int — the oracle's mirror.  Must
    stay bit-identical to the array version (tested)."""
    a = ((tuple_hash & 0xFFFFFFFF) * 0x85EBCA6B) & 0xFFFFFFFF
    a ^= a >> 13
    b = ((tuple_hash & 0xFFFFFFFF) * 0xC2B2AE35) & 0xFFFFFFFF
    b ^= b >> 16
    return int(a % cold_size), int(b % cold_size)


def _check_policy(policy: str) -> None:
    if policy not in COLD_POLICIES:
        raise ValueError(f"policy must be one of {COLD_POLICIES}, "
                         f"got {policy!r}")


def _choose_slot(tuple_id: jax.Array, count: jax.Array, stamp: jax.Array,
                 h: jax.Array, freed: Optional[jax.Array] = None
                 ) -> jax.Array:
    """Insert destination for tuple ``h`` in the cold table given by its
    small leaves: its own entry if present (never duplicate), else the first
    empty candidate, else the candidate with the smaller stamp (tie prefers
    candidate 1).  ``freed`` names a slot to read as empty although its
    leaves are not cleared yet."""
    a, b = cold_slots(h, tuple_id.shape[0])
    occ_a = count[a] > 0
    occ_b = count[b] > 0
    if freed is not None:
        occ_a &= a != freed
        occ_b &= b != freed
    match_a = occ_a & (tuple_id[a] == h)
    match_b = occ_b & (tuple_id[b] == h)
    victim = jnp.where(stamp[a] <= stamp[b], a, b)
    return jnp.where(match_a, a,
                     jnp.where(match_b, b,
                               jnp.where(~occ_a, a,
                                         jnp.where(~occ_b, b, victim))))


def _last_writer(dst: jax.Array, C: int) -> jax.Array:
    """``dst`` with every destination that a later entry also writes set to
    the out-of-range sentinel ``C``, so one vectorised scatter keeps the
    last writer of each slot, as sequential inserts would."""
    dup_later = jnp.triu(dst[None, :] == dst[:, None], k=1).any(axis=1)
    return jnp.where(dup_later, C, dst)


def _acting_first(act: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(order, n)``: a permutation that puts the ``act`` entries first,
    keeping their order, and how many there are — a serial walk over
    ``order[:n]`` makes one trip per acting entry."""
    return (jnp.argsort(~act, stable=True),
            act.sum().astype(jnp.int32))


def promote_pass(hot: ft.TrackerState, cold: ColdState,
                 packets: ft.PacketBatch,
                 keep: Optional[jax.Array] = None, *, policy: str
                 ) -> tuple[ft.TrackerState, ColdState, jax.Array, jax.Array]:
    """Step 1 of the two-level step: walk the batch's segment heads in
    ascending hot-slot order; where the head tuple is not live in hot but
    present in cold, load the cold entry into the hot slot (spilling a
    displaced occupant into cold first) and free the cold source.  Returns
    ``(hot, cold, promoted_count, walked)``, ``walked`` being the serial
    trips made.

    Runs *before* the merge, so the merge sees the promoted flow as a hit
    and its packet count keeps growing where the single-level tracker would
    have restarted from zero.  Only the segment head consults cold: a second
    tuple colliding onto the same slot mid-batch establishes fresh exactly
    as today (its stale cold twin, if any, is scrubbed after the merge).

    Implementation note — the sequential walk only carries the *small* (C,)
    bookkeeping leaves (tuple_id / count / last_ts / stamp / tick), where
    every 2-choice decision lives; the wide leaves (features / series /
    sizes / payload) are moved afterwards with vectorized scatters.  (A loop
    that both gathers and scatters the wide cold leaves per iteration makes
    XLA copy the whole cold bank each step — ~seconds at 10^5+ slots.)
    The split is exact, not an approximation, because within one pass:
      * segment heads own *distinct* hot slots, so hot reads/writes never
        interleave across iterations;
      * a promoted source slot always still holds its pre-pass record (a
        displaced occupant's tuple hashes to an *earlier* head's hot slot,
        so it can never be a later head's promotion source);
      * when two displaced occupants land on the same cold slot the later
        insert wins — resolved below with a last-writer mask.
    The walk visits only the heads that can promote, one trip each: the
    *candidates*, heads whose tuple is not live in hot and is in cold
    before the pass, found vectorized on the pre-pass state.  Skipping the
    rest is exact:
      * hot is read-only during the walk, so a head that is live in hot
        stays live;
      * over the walk a later head's cold entry can only disappear (a freed
        source, or a victim a displaced occupant evicts), never appear: a
        displaced occupant's tuple hashes to its own head's hot slot, which
        no other head owns, so it never equals another head's tuple;
      * a head that does not promote leaves the carry untouched (nothing
        freed, nothing inserted, ``tick`` unchanged).
    A candidate whose entry disappeared mid-walk still takes its trip and
    promotes nothing.
    The oracle differential in tests/test_cold_store.py pins all of this."""
    _check_policy(policy)
    F = hot.tuple_id.shape[0]
    C = cold.tuple_id.shape[0]
    P = packets.ts.shape[0]
    slots = ft.hash_slot(packets.tuple_hash, F)
    if keep is not None:
        slots = jnp.where(keep, slots, F)
    order = jnp.argsort(slots, stable=True)
    s_slot = slots[order]
    s_hash = packets.tuple_hash[order]
    first = jnp.concatenate([jnp.ones((1,), bool), s_slot[1:] != s_slot[:-1]])
    head = first & (s_slot < F)
    fss = jnp.where(s_slot < F, s_slot, 0)
    # the hot slot's pre-pass occupant; hot is read-only in the walk
    occ_tid, occ_cnt, occ_ts = (hot.tuple_id[fss], hot.count[fss],
                                hot.last_ts[fss])
    hit = (occ_cnt > 0) & (occ_tid == s_hash)
    s_a, s_b = cold_slots(s_hash, C)
    in_a = (cold.count[s_a] > 0) & (cold.tuple_id[s_a] == s_hash)
    in_b = (cold.count[s_b] > 0) & (cold.tuple_id[s_b] == s_hash)
    walk, n = _acting_first(head & ~hit & (in_a | in_b))

    def body(carry):
        j, c_tid, c_cnt, c_ts, c_stamp, tick, promo, srcs, dsts = carry
        i = walk[j]
        h, a, b = s_hash[i], s_a[i], s_b[i]
        in_a = (c_cnt[a] > 0) & (c_tid[a] == h)
        in_b = (c_cnt[b] > 0) & (c_tid[b] == h)
        p = in_a | in_b  # false where an earlier trip removed the entry
        src = jnp.where(in_a, a, b)
        disp = p & (occ_cnt[i] > 0)

        # free the source, then 2-choice-insert the displaced occupant (its
        # probe legitimately sees — and may reuse — the just-freed slot).
        # All gathers probe the PRE-clear state and read the freed slot as
        # empty, so each buffer sees one gather phase then one scatter phase
        # per iteration — the shape XLA keeps in place; interleaving gathers
        # between the clear and insert scatters makes it copy the (C,)
        # leaves every iteration.
        csrc = jnp.where(p, src, C)
        dst = jnp.where(disp, _choose_slot(c_tid, c_cnt, c_stamp, occ_tid[i],
                                           freed=csrc), C)
        stamp = occ_ts[i] if policy == "age" else tick
        c_tid = c_tid.at[csrc].set(0, mode="drop").at[dst].set(
            occ_tid[i], mode="drop")
        c_cnt = c_cnt.at[csrc].set(0, mode="drop").at[dst].set(
            occ_cnt[i], mode="drop")
        c_ts = c_ts.at[dst].set(occ_ts[i], mode="drop")
        c_stamp = c_stamp.at[csrc].set(0, mode="drop").at[dst].set(
            stamp, mode="drop")
        return (j + 1, c_tid, c_cnt, c_ts, c_stamp,
                tick + disp.astype(jnp.int32), promo.at[i].set(p),
                srcs.at[i].set(src), dsts.at[i].set(dst))

    carry = (jnp.int32(0), cold.tuple_id, cold.count, cold.last_ts,
             cold.stamp, cold.tick, jnp.zeros((P,), bool),
             jnp.zeros((P,), jnp.int32), jnp.full((P,), C, jnp.int32))
    _, c_tid, c_cnt, c_ts, c_stamp, tick, promo, srcs, dsts = lax.while_loop(
        lambda c: c[0] < n, body, carry)

    # phase 2: promoted entries hot[fs] <- pre-pass cold[src].  Gathering
    # from the pre-pass cold is exact — a promotion source still holds its
    # pre-pass record (see the implementation note above).
    tgts = jnp.where(promo, fss, F)
    srcs_safe = jnp.where(promo, srcs, 0)

    def load(hot_leaf, cold_leaf):
        return hot_leaf.at[tgts].set(cold_leaf[srcs_safe], mode="drop")

    # displaced occupants cold[dst] <- pre-pass hot[fs]; duplicate dst rows
    # resolve to the LAST writer, matching the sequential small-leaf walk
    dsts_w = _last_writer(dsts, C)
    fss_safe = jnp.where(dsts_w < C, fss, 0)

    def store(cold_leaf, hot_leaf):
        return cold_leaf.at[dsts_w].set(hot_leaf[fss_safe], mode="drop")

    new_hot = hot._replace(
        tuple_id=load(hot.tuple_id, cold.tuple_id),
        count=load(hot.count, cold.count),
        last_ts=load(hot.last_ts, cold.last_ts),
        features=load(hot.features, cold.features),
        series=load(hot.series, cold.series),
        sizes=load(hot.sizes, cold.sizes),
        payload=load(hot.payload, cold.payload))
    new_cold = cold._replace(
        tuple_id=c_tid, count=c_cnt, last_ts=c_ts, stamp=c_stamp, tick=tick,
        features=store(cold.features, hot.features),
        series=store(cold.series, hot.series),
        sizes=store(cold.sizes, hot.sizes),
        payload=store(cold.payload, hot.payload))
    return new_hot, new_cold, promo.sum().astype(jnp.int32), n


def apply_spills(cold: ColdState, spills: ft.SpillRecords, *,
                 policy: str) -> tuple[ColdState, jax.Array]:
    """Step 3: fold one merge's eviction records into cold, sequentially in
    packet order (later spills may evict earlier ones — exactly the scalar
    semantics the oracle mirrors).  Returns ``(cold, inserted_count)``; the
    serial walk makes one trip per inserted record.

    As in :func:`promote_pass`, the walk carries only the small leaves that
    :func:`_choose_slot` reads (tuple_id / count / stamp / tick) and records
    each record's destination; ``last_ts`` and the wide leaves are written
    afterwards in one scatter each, the later record winning a shared
    destination.  Exact: what lands in cold comes from the spill records,
    never from cold, so no trip reads a leaf an earlier trip wrote."""
    _check_policy(policy)
    C = cold.tuple_id.shape[0]
    P = spills.mask.shape[0]
    walk, n = _acting_first(spills.mask)

    def body(carry):
        j, c_tid, c_cnt, c_stamp, tick, dsts = carry
        i = walk[j]
        h = spills.tuple_id[i]
        dst = _choose_slot(c_tid, c_cnt, c_stamp, h)
        stamp = spills.last_ts[i] if policy == "age" else tick
        return (j + 1, c_tid.at[dst].set(h),
                c_cnt.at[dst].set(spills.count[i]),
                c_stamp.at[dst].set(stamp), tick + 1, dsts.at[i].set(dst))

    carry = (jnp.int32(0), cold.tuple_id, cold.count, cold.stamp, cold.tick,
             jnp.full((P,), C, jnp.int32))
    _, c_tid, c_cnt, c_stamp, tick, dsts = lax.while_loop(
        lambda c: c[0] < n, body, carry)
    dsts = _last_writer(dsts, C)

    def put(cold_leaf, rec):
        return cold_leaf.at[dsts].set(rec, mode="drop")

    return cold._replace(
        tuple_id=c_tid, count=c_cnt, stamp=c_stamp, tick=tick,
        last_ts=put(cold.last_ts, spills.last_ts),
        features=put(cold.features, spills.features),
        series=put(cold.series, spills.series),
        sizes=put(cold.sizes, spills.sizes),
        payload=put(cold.payload, spills.payload)), n


def scrub_live(cold: ColdState, hot: ft.TrackerState,
               packets: ft.PacketBatch,
               keep: Optional[jax.Array] = None) -> ColdState:
    """Step 4: clear any cold entry whose tuple is live in hot after the
    merge.  Only batch tuples can have newly established, so a (P,)-wide
    vectorized check covers every possible violation of the no-twin
    invariant; clears are idempotent, so no sequencing is needed."""
    F = hot.tuple_id.shape[0]
    C = cold.tuple_id.shape[0]
    h = packets.tuple_hash
    k = jnp.ones(h.shape, bool) if keep is None else keep
    fs = ft.hash_slot(h, F)
    live = k & (hot.count[fs] > 0) & (hot.tuple_id[fs] == h)
    a, b = cold_slots(h, C)
    hit_a = live & (cold.count[a] > 0) & (cold.tuple_id[a] == h)
    hit_b = live & (cold.count[b] > 0) & (cold.tuple_id[b] == h)
    ca = jnp.where(hit_a, a, C)
    cb = jnp.where(hit_b, b, C)

    def clear(leaf):
        return leaf.at[ca].set(0, mode="drop").at[cb].set(0, mode="drop")

    return cold._replace(tuple_id=clear(cold.tuple_id),
                         count=clear(cold.count),
                         stamp=clear(cold.stamp))


def cold_occupancy(cold: ColdState) -> jax.Array:
    """() int32 — live cold entries (monitoring / tests)."""
    return (cold.count > 0).sum().astype(jnp.int32)
