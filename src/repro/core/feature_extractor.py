"""Feature extracting domain (paper §3.1): meta-feature extraction, whole-set
derivation, and the TPU-parallel (segmented) tracker update.

Two execution modes over the same :class:`~repro.core.flow_tracker.TrackerState`:

  * ``extract_scan``       — order-exact oracle; ``lax.scan`` over packets,
                             mirroring the FPGA's serial line-rate fold.  With
                             ``use_pallas`` the 16-lane ALU fold additionally
                             replays through the ``flow_features`` Pallas
                             kernel (exact, any micro-op program) and the
                             kernel result replaces the feature table — so
                             the kernel is exercised on the real
                             establish/evict stream (equality with the scan
                             oracle is asserted in tests).
  * ``segmented_update``   — the TPU-native fast path used by the streaming
                             pipeline: packets are sorted by slot once
                             (stable, so per-flow batch order is preserved),
                             then the whole microbatch merges into the live
                             ``TrackerState`` in one vectorized pass — counts,
                             series/payload memories and tuple ids by rank
                             arithmetic + scatter, feature lanes by segment
                             reductions (or by the Pallas ALU fold under
                             ``use_pallas``, which supports arbitrary
                             programs).  Slots whose batch segment mixes more
                             than one tuple hash take the scan oracle's values
                             instead (a ``lax.cond`` fallback), so the result
                             is *bit-exact* to the oracle in every case — the
                             fallback merely costs the scan when a collision
                             actually occurs.

``extract_segmented`` (empty-table extraction, the original API) is the thin
wrapper ``segmented_update(init_state(), packets)``.

Derived (whole-set) features — Table 7 — come out of the 16-lane history
register by configuration: mean = flow_size/pkt_count, duration = Σ intervals,
etc.  ``derive_whole_features`` materializes the standard derived vector.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import flow_tracker as ft
from repro.kernels.flow_features.ops import (
    HIST,
    default_program,
    default_program_np,
    fold_features,
)

INT_MAX = jnp.iinfo(jnp.int32).max


@dataclass(frozen=True)
class ExtractorConfig:
    table_size: int = 8192  # paper: 8k-depth flow-state table
    top_n: int = 20  # packets per flow tracked for series features
    top_k: int = 15  # packets contributing payload rows
    pay_bytes: int = 16  # payload bytes per packet (paper use-case 3: 16)
    use_pallas: bool = False
    interpret: Optional[bool] = None  # None: derive from the ambient runtime


class SegmentedOut(NamedTuple):
    """Aggregate tracker events of one segmented microbatch merge."""

    new_flows: jax.Array  # () int32 — flows established this batch
    evicted: jax.Array  # () int32 — stale flows recycled by collision
    fallback_slots: jax.Array  # () int32 — slots that took the scan fallback


def check_default_program(program: jax.Array) -> None:
    """The jnp segment-reduction lanes hard-code the default program's
    semantics; refuse a different concrete program loudly instead of silently
    diverging.  (A traced program cannot be inspected — callers jitting over
    the program must route through ``use_pallas``, which folds any program.)"""
    try:
        arr = np.asarray(program)
    except Exception:
        return
    if not np.array_equal(arr, default_program_np()):
        raise ValueError(
            "segmented_update without use_pallas supports only the default "
            "micro-op program (its feature lanes are segment reductions, not "
            "an ALU replay); set use_pallas=True or use the scan tracker")


FALLBACK_MODES = ("auto", "always", "never")


def _mixed_segment_heads(s_slot: jax.Array, s_hash: jax.Array,
                         table_size: int) -> jax.Array:
    """(P,) bool over slot-sorted packets — True where a tuple-hash flip
    occurs inside one slot segment (sentinel rows >= table_size excluded).
    The ONE in-batch collision predicate: :func:`segmented_update`'s scan
    fallback and :func:`batch_collisions` must agree, so both call this."""
    return jnp.concatenate([
        jnp.zeros((1,), bool),
        (s_slot[1:] == s_slot[:-1]) & (s_hash[1:] != s_hash[:-1])
        & (s_slot[1:] < table_size)])


def batch_collisions(packets: ft.PacketBatch, table_size: int,
                     keep: Optional[jax.Array] = None) -> jax.Array:
    """() bool — does this (optionally masked) microbatch contain an in-batch
    slot collision (two distinct tuple hashes mapping to one slot)?  This is
    exactly the predicate :func:`segmented_update`'s scan fallback guards on
    (both share :func:`_mixed_segment_heads`), exposed so batched callers
    (the sharded pipeline's vmapped lanes) can hoist the branch *outside*
    their vmap — a vmapped ``lax.cond`` lowers to a select that pays for
    both branches, i.e. the whole scan oracle on every batch."""
    slots = ft.hash_slot(packets.tuple_hash, table_size)
    if keep is not None:
        slots = jnp.where(keep, slots, table_size)
    order = jnp.argsort(slots, stable=True)
    return _mixed_segment_heads(slots[order], packets.tuple_hash[order],
                                table_size).any()


def segmented_update(
    state: ft.TrackerState,
    packets: ft.PacketBatch,
    program: Optional[jax.Array] = None,
    *,
    top_n: int,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
    keep: Optional[jax.Array] = None,
    fallback: str = "auto",
    with_spills: bool = False,
):
    """Merge a whole microbatch into the live tracker state in one vectorized
    pass — the TPU-parallel replacement for the per-packet scan.

    Exactness contract (tested differentially against
    :func:`flow_tracker.process_packets` and the pure-Python oracle): the
    returned state and event counts are bit-identical to scanning the batch
    packet by packet.  Slots whose batch segment contains more than one
    distinct tuple hash (an in-batch collision — establish/evict flips mid-
    segment) cannot be expressed as a single segment reduction; those slots
    take the scan oracle's values via a ``lax.cond`` fallback that only
    executes when a collision is actually present in the batch.

    ``keep`` (optional, (P,) bool) drops packets without changing shapes:
    masked-out packets sort to the out-of-range sentinel slot, so every
    segment reduction and scatter ignores them — the exactness contract then
    holds against scanning only the kept packets.  This is how the sharded
    lanes consume hash-partitioned (padded) microbatches.

    ``fallback`` controls the collision branch: ``"auto"`` (default) guards
    it with a ``lax.cond``; ``"always"``/``"never"`` select a branch
    statically, for callers that hoist the :func:`batch_collisions`
    predicate outside a vmap.  ``"never"`` is only exact when the batch
    really has no in-batch collision — callers own that guard.

    ``with_spills`` (static) additionally returns the merge's
    :class:`~repro.core.flow_tracker.SpillRecords`, bit-identical to the
    scan tracker's (differentially tested): a non-colliding slot's eviction
    happens exactly at its segment-head packet, so the pre-batch occupant
    scatters back to that packet's original batch position; colliding slots
    take the scan fallback's per-packet records.  Returns
    ``(state, SegmentedOut)`` by default,
    ``(state, SegmentedOut, SpillRecords)`` under ``with_spills``.
    """
    if fallback not in FALLBACK_MODES:
        raise ValueError(f"fallback must be one of {FALLBACK_MODES}, "
                         f"got {fallback!r}")
    if program is None:
        program = default_program()
    if not use_pallas:
        check_default_program(program)
    if interpret is None:  # platform-derived, like every other entry point
        from repro.runtime import resolve_config

        interpret = resolve_config(None).interpret
    F = state.tuple_id.shape[0]
    top_k = state.payload.shape[1]
    pay_bytes = state.payload.shape[2]
    P = packets.ts.shape[0]
    masked = keep is not None  # unmasked callers keep the kernel fast path
    if keep is None:
        keep = jnp.ones((P,), bool)

    slots = ft.hash_slot(packets.tuple_hash, F)
    # masked-out packets take the sentinel slot F: they sort to the end and
    # every segment reduction / scatter (num_segments == F, mode="drop")
    # ignores them
    slots_eff = jnp.where(keep, slots, F)
    # stable sort by slot: per-flow packets stay in batch (arrival) order
    order = jnp.argsort(slots_eff, stable=True)
    s = jax.tree_util.tree_map(lambda a: a[order], packets)
    s_slot = slots_eff[order]
    s_keep = keep[order]

    first = jnp.concatenate([jnp.ones((1,), bool), s_slot[1:] != s_slot[:-1]])
    ones = jnp.ones((P,), jnp.int32)
    counts_b = jax.ops.segment_sum(ones, s_slot, F, indices_are_sorted=True)
    touched = counts_b > 0

    # in-batch collision: a segment holding >1 distinct tuple hash (the
    # shared predicate — batch_collisions must see exactly these flips)
    mixed = _mixed_segment_heads(s_slot, s.tuple_hash, F)
    collide = jnp.zeros((F,), jnp.int32).at[s_slot].max(
        mixed.astype(jnp.int32), mode="drop") > 0

    # single-hash segments: any reduction of equal values recovers the hash
    h_f = jax.ops.segment_max(s.tuple_hash, s_slot, F, indices_are_sorted=True)
    occupied = state.count > 0
    hit = touched & occupied & (state.tuple_id == h_f)
    establish = touched & ~hit  # first packet of the segment establishes
    evicted_f = touched & occupied & ~hit

    count0 = jnp.where(hit, state.count, 0)
    feats_base = jnp.where(establish[:, None], ft.fresh_feature_word()[None, :],
                           state.features)
    series_base = jnp.where(establish[:, None], 0, state.series)
    sizes_base = jnp.where(establish[:, None], 0, state.sizes)
    pay_base = jnp.where(establish[:, None, None], 0, state.payload)

    # inter-arrival per packet: within the segment from the previous packet,
    # at the segment head from the live flow's last_ts (0 at establish)
    prev_ts = jnp.concatenate([jnp.zeros((1,), jnp.int32), s.ts[:-1]])
    head_intv = jnp.where(hit[s_slot], s.ts - state.last_ts[s_slot], 0)
    intv = jnp.where(first, head_intv, s.ts - prev_ts)

    start = jnp.cumsum(counts_b) - counts_b
    rank = jnp.arange(P, dtype=jnp.int32) - start[s_slot]
    g_rank = count0[s_slot] + rank  # per-flow packet index incl. history
    last_idx = jnp.clip(jnp.cumsum(counts_b) - 1, 0, max(P - 1, 0))

    if use_pallas:
        # ALU fold through the Pallas kernel: exact for any program (per-slot
        # order is the batch order; establish resets are pre-applied in
        # feats_base; colliding slots are overwritten by the fallback)
        meta = jax.vmap(ft.build_meta)(s, intv)
        feats = fold_features(program, s_slot, meta, feats_base,
                              keep=s_keep if masked else None,
                              interpret=interpret)
    else:
        segsum = lambda x: jax.ops.segment_sum(x, s_slot, F,
                                               indices_are_sorted=True)
        segmax = lambda x: jax.ops.segment_max(x, s_slot, F,
                                               indices_are_sorted=True)
        segmin = lambda x: jax.ops.segment_min(x, s_slot, F,
                                               indices_are_sorted=True)

        feats = feats_base

        def upd(f, lane, val):
            return f.at[:, lane].set(jnp.where(touched, val, f[:, lane]))

        base = lambda lane: feats_base[:, lane]
        feats = upd(feats, HIST["flow_dur"], base(HIST["flow_dur"]) + segsum(intv))
        feats = upd(feats, HIST["pkt_count"], count0 + counts_b)
        feats = upd(feats, HIST["flow_size"], base(HIST["flow_size"]) + segsum(s.size))
        feats = upd(feats, HIST["max_size"],
                    jnp.maximum(base(HIST["max_size"]), segmax(s.size)))
        feats = upd(feats, HIST["min_size"],
                    jnp.minimum(base(HIST["min_size"]), segmin(s.size)))
        feats = upd(feats, HIST["max_intv"],
                    jnp.maximum(base(HIST["max_intv"]), segmax(intv)))
        feats = upd(feats, HIST["min_intv"],
                    jnp.minimum(base(HIST["min_intv"]), segmin(intv)))
        feats = upd(feats, HIST["last_ts"], s.ts[last_idx])
        feats = upd(feats, HIST["size_fwd"],
                    base(HIST["size_fwd"]) + segsum(jnp.where(s.dir == 0, s.size, 0)))
        feats = upd(feats, HIST["size_bwd"],
                    base(HIST["size_bwd"]) + segsum(jnp.where(s.dir == 1, s.size, 0)))
        feats = upd(feats, HIST["flags_acc"], base(HIST["flags_acc"]) + segsum(s.flags))
        feats = upd(feats, HIST["last_size"], s.size[last_idx])
        feats = upd(feats, HIST["payload_bytes"],
                    base(HIST["payload_bytes"]) + segsum(jnp.minimum(s.size, pay_bytes)))
        feats = upd(feats, HIST["proto"], s.proto[last_idx])

    # series/payload memories by per-flow rank; overflow ranks are dropped
    # (never overwrite the oldest stored packets — oracle semantics)
    idx_n = jnp.where(g_rank < top_n, g_rank, top_n)
    series = series_base.at[s_slot, idx_n].set(intv, mode="drop")
    sizes = sizes_base.at[s_slot, idx_n].set(s.size, mode="drop")
    idx_k = jnp.where(g_rank < top_k, g_rank, top_k)
    payload = pay_base.at[s_slot, idx_k].set(s.payload, mode="drop")

    seg_state = ft.TrackerState(
        tuple_id=jnp.where(touched, h_f, state.tuple_id),
        count=jnp.where(touched, count0 + counts_b, state.count),
        last_ts=jnp.where(touched, s.ts[last_idx], state.last_ts),
        features=feats,
        series=series,
        sizes=sizes,
        payload=payload,
    )
    new_nc = jnp.sum(establish & ~collide).astype(jnp.int32)
    ev_nc = jnp.sum(evicted_f & ~collide).astype(jnp.int32)
    pkt_collides = collide[slots]  # original batch order

    if with_spills:
        # a non-colliding slot's eviction happens exactly at its segment-head
        # packet (scan semantics: the first batch packet touching the slot
        # displaces the stale occupant), so the pre-batch occupant snapshot
        # scatters back to that packet's original batch position; colliding
        # slots are overwritten per-packet by the scan fallback below
        safe_sl = jnp.where(s_slot < F, s_slot, 0)
        ev_head = first & (s_slot < F) & evicted_f[safe_sl]
        pos = jnp.where(ev_head, order, P)

        def scat_like(table):
            return jnp.zeros((P,) + table.shape[1:], table.dtype).at[pos].set(
                table[safe_sl], mode="drop")

        seg_spills = ft.SpillRecords(
            mask=jnp.zeros((P,), bool).at[pos].set(ev_head, mode="drop"),
            slot=jnp.full((P,), F, jnp.int32).at[pos].set(s_slot, mode="drop"),
            tuple_id=scat_like(state.tuple_id),
            count=scat_like(state.count),
            last_ts=scat_like(state.last_ts),
            features=scat_like(state.features),
            series=scat_like(state.series),
            sizes=scat_like(state.sizes),
            payload=scat_like(state.payload),
        )
    else:
        seg_spills = None

    def with_fallback(_):
        with jax.named_scope("track.fallback"):  # device-trace name
            if with_spills:
                scan_state, outs, scan_spills = ft.process_packets(
                    state, packets, program, top_n=top_n, keep=keep,
                    with_spills=True)
            else:
                scan_state, outs = ft.process_packets(state, packets, program,
                                                      top_n=top_n, keep=keep)
                scan_spills = None

            def pick(seg_leaf, scan_leaf):
                m = collide.reshape((F,) + (1,) * (seg_leaf.ndim - 1))
                return jnp.where(m, scan_leaf, seg_leaf)

            merged = jax.tree_util.tree_map(pick, seg_state, scan_state)
            new = new_nc + jnp.sum(outs.new_flow & pkt_collides).astype(jnp.int32)
            ev = ev_nc + jnp.sum(outs.evicted & pkt_collides).astype(jnp.int32)
            if not with_spills:
                return merged, new, ev, None

            def pick_pkt(seg_leaf, scan_leaf):
                m = pkt_collides.reshape((P,) + (1,) * (seg_leaf.ndim - 1))
                return jnp.where(m, scan_leaf, seg_leaf)

            return merged, new, ev, jax.tree_util.tree_map(pick_pkt, seg_spills,
                                                           scan_spills)

    def without_fallback(_):
        return seg_state, new_nc, ev_nc, seg_spills

    if fallback == "always":
        state1, new_flows, evicted, spills = with_fallback(None)
    elif fallback == "never":
        state1, new_flows, evicted, spills = without_fallback(None)
    else:
        state1, new_flows, evicted, spills = lax.cond(
            collide.any(), with_fallback, without_fallback, operand=None)
    out = SegmentedOut(new_flows=new_flows, evicted=evicted,
                       fallback_slots=jnp.sum(collide).astype(jnp.int32))
    if with_spills:
        return state1, out, spills
    return state1, out


class FeatureExtractor:
    def __init__(self, cfg: ExtractorConfig = ExtractorConfig(), program: Optional[jax.Array] = None):
        self.cfg = cfg
        self.program = program if program is not None else default_program()

    def init_state(self) -> ft.TrackerState:
        c = self.cfg
        return ft.init_state(c.table_size, c.top_n, c.top_k, c.pay_bytes)

    def _interpret(self) -> bool:
        if self.cfg.interpret is not None:
            return self.cfg.interpret
        from repro.runtime import resolve_config

        return resolve_config(None).interpret

    # ------------------------------------------------------------------ scan
    def extract_scan(self, state: ft.TrackerState, packets: ft.PacketBatch):
        """Order-exact oracle (``lax.scan``).  Under ``use_pallas`` the
        feature table is additionally recomputed by replaying the ALU fold
        through the Pallas ``flow_features`` kernel and the kernel's result
        replaces the scanned feature lanes — identical by construction
        (asserted in tests, not at runtime), so the kernel is exercised on
        the real establish/evict stream.  Tracking metadata (counts,
        series, payload, tuple ids) always comes from the scan: it is the
        inherently sequential part the FPGA pipelines in hardware."""
        state2, outs = ft.process_packets(state, packets, self.program,
                                          top_n=self.cfg.top_n)
        if not self.cfg.use_pallas:
            return state2, outs
        P = packets.ts.shape[0]
        F = self.cfg.table_size
        pos = jnp.arange(P, dtype=jnp.int32)
        # a flow's feature word only reflects packets since its LAST establish
        # (each establish resets the word) — replay exactly those
        last_est = jnp.full((F,), -1, jnp.int32).at[outs.slot].max(
            jnp.where(outs.new_flow, pos, -1))
        keep = pos >= last_est[outs.slot]
        feats_base = jnp.where((last_est >= 0)[:, None],
                               ft.fresh_feature_word()[None, :],
                               state.features)
        meta = jax.vmap(ft.build_meta)(packets, outs.arv_intv)
        feats = fold_features(self.program, outs.slot, meta, feats_base,
                              keep=keep, interpret=self._interpret())
        return state2._replace(features=feats), outs

    # ------------------------------------------------------- segmented (TPU)
    def segmented_update(self, state: ft.TrackerState, packets: ft.PacketBatch):
        """Vectorized microbatch merge into live state (see module-level
        :func:`segmented_update`); honours ``cfg.use_pallas``."""
        return segmented_update(state, packets, self.program,
                                top_n=self.cfg.top_n,
                                use_pallas=self.cfg.use_pallas,
                                interpret=self._interpret())

    def extract_segmented(self, packets: ft.PacketBatch):
        """Parallel extraction for a *batch* of packets starting from an empty
        table.  Returns (features (F,16), series (F,top_n), sizes, payload,
        counts (F,)).  Exact against the scan oracle, including in-batch slot
        collisions (those take the scan fallback inside
        :func:`segmented_update`)."""
        state, _ = self.segmented_update(self.init_state(), packets)
        return (state.features, state.series, state.sizes, state.payload,
                state.count)


def derive_whole_features(feats: jax.Array) -> jax.Array:
    """Derive the float 'whole feature set' vector (Table 7 core subset) from
    the 16-lane history register.  Returns (..., 12) float32."""
    f = feats.astype(jnp.float32)
    count = jnp.maximum(f[..., HIST["pkt_count"]], 1.0)
    dur = f[..., HIST["flow_dur"]]
    size = f[..., HIST["flow_size"]]
    out = jnp.stack(
        [
            dur,  # flow duration time
            f[..., HIST["pkt_count"]],  # total packets
            size,  # flow size
            size / count,  # mean packet length
            f[..., HIST["max_size"]],
            jnp.where(f[..., HIST["min_size"]] >= INT_MAX, 0.0, f[..., HIST["min_size"]]),
            f[..., HIST["max_intv"]],
            jnp.where(f[..., HIST["min_intv"]] >= INT_MAX, 0.0, f[..., HIST["min_intv"]]),
            dur / count,  # mean inter-arrival
            f[..., HIST["size_fwd"]],
            f[..., HIST["size_bwd"]],
            f[..., HIST["flags_acc"]],
        ],
        axis=-1,
    )
    return out


def packet_meta_features(packets: ft.PacketBatch) -> jax.Array:
    """Per-packet feature vector for packet-granularity models (use-case 1's
    six-dimension input: size, direction, flags, proto, payload_len, intv=0)."""
    pay_len = jnp.minimum(packets.size, packets.payload.shape[-1])
    return jnp.stack(
        [
            packets.size.astype(jnp.float32),
            packets.dir.astype(jnp.float32),
            packets.flags.astype(jnp.float32),
            packets.proto.astype(jnp.float32),
            pay_len.astype(jnp.float32),
            jnp.zeros_like(packets.size, jnp.float32),
        ],
        axis=-1,
    )
