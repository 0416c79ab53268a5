"""Sharded multi-lane serving pipeline (paper §2.2 / §4: parallel extractor
lanes over a multi-bank memory fabric).

:class:`ShardedOctopusPipeline` horizontally scales the streaming loop by
hash-partitioning incoming packets into ``num_shards`` lanes
(``shard = tuple_hash % num_shards`` — a flow's packets always land in the
same shard, so there is **no cross-shard flow state**), running each lane's
step core over its own :class:`~repro.core.flow_tracker.TrackerState` bank,
and merging the per-lane drain results into one masked emission, so
``decide`` and the rule-table feedback are unchanged downstream.

Lane execution backend (selected through ``repro.runtime.platform``):

  * ``"shard_map"`` — one device per lane on a ``lanes`` mesh axis
    (:func:`repro.launch.mesh.make_lanes_mesh`): each lane's tracker bank
    lives on its own device, the software shape of the paper's per-bank
    extractor lanes.
  * ``"vmap"``      — single-device fallback: lanes are batched.  For the
    ``"scan"`` tracker this still cuts the sequential depth from the global
    batch to the per-lane capacity (``vmap`` of a ``lax.scan`` is one scan
    with a batched body), which is where the CPU-smoke scaling comes from.

Exactness contract (differentially tested against the single-lane oracle in
``tests/test_sharded.py``): whenever (a) flows that share a table slot also
share a shard — always true under collision-free traffic, and for any
same-shard collision — and (b) the drain budget keeps up with the ready rate
(no lane ever holds back a ready flow: the global ``max_ready`` splits into
``max_ready / num_shards`` per lane, so a backlogged lane drains later than
the oracle's global lowest-slots-first order would, shifting the emitted
count/feature snapshot), the union of drained flows, the residual per-shard
table contents, and every per-flow decision are bit-identical to
:class:`~repro.serving.pipeline.OctopusPipeline` consuming the same stream.
The differential tests assert the no-backlog precondition on both sides
instead of trusting it.
Each lane keeps a full ``table_size`` bank with the *same* slot mapping as
the single-lane table, so a flow's slot number is shard-invariant; what a
lane cannot see is an eviction by a flow of another shard, which is exactly
the cross-shard collision case excluded above.

Skew handling: per-lane capacity (``lane_batch``) defaults to the full
``batch_size`` — skew-proof, one fused dispatch per step.  A smaller
``lane_batch`` trades padding for rounds: overflowing lanes spill into
merge-only rounds ahead of the fused drain step
(:func:`repro.data.traffic.partition_batch` splits each lane's FIFO into
capacity-sized windows; the tracker merge composes sequentially, so the
result stays bit-exact and the drain still happens once per global batch).
The served path (``step_masked``, one padded bucket per dispatch) always
partitions at full bucket capacity: each lane gets ``bucket`` rows, about
``bucket / num_shards`` of them kept, so every lane's merge runs over the
whole bucket.  ``PipelineStats.lane_rows_max`` adds each dispatch's fullest
lane's kept rows, counted on the host (lane fill ``packets / (packets +
padded)``, skew ``num_shards * lane_rows_max / packets``).

Measurement: the merge of the lane outputs (:meth:`_merge_out`) runs under
the ``lanes.merge`` named scope, outside the ``shard_map``; under it sit
the cross-device collectives the merge needs (verdicts and source rows
gathered for the scatter back to batch order, counters summed), so the
device trace names their time.  No collective touches the carried banks.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import feature_extractor as fx
from repro.core import flow_tracker as ft
from repro.data.traffic import ShardedBatch, partition_batch, shard_of
from repro.distributed import sharding as shd
from repro.launch.mesh import make_lanes_mesh
from repro.runtime import (RoutePlan, RuntimeConfig, lane_scope, name_scope,
                           platform, span)
from repro.serving.pipeline import (
    COUNTERS,
    InflightDispatch,
    OctopusPipeline,
    PipelineConfig,
    PipelineStepOutput,
    _feedback_args,
    host_pack,
)

LANE_BACKENDS = ("vmap", "shard_map")


class ShardedOctopusPipeline(OctopusPipeline):
    """Hash-partitioned multi-lane :class:`OctopusPipeline`.

    Same public surface as the single-lane pipeline — ``step`` takes the
    same global ``batch_size`` microbatch and returns a merged
    :class:`PipelineStepOutput` with identical shapes (``pkt_actions`` in
    original batch order; ``max_ready`` drained rows = ``num_shards`` lanes
    × ``max_ready / num_shards`` budget each) — so the differential harness
    can drive both from one seeded :class:`~repro.data.traffic.TrafficGenerator`.
    """

    def __init__(self, packet_params: Any, flow_params: Any,
                 cfg: PipelineConfig = PipelineConfig(), *,
                 num_shards: int,
                 lane_batch: Optional[int] = None,
                 backend: Optional[str] = None,
                 config: Optional[RuntimeConfig] = None,
                 program: Optional[jax.Array] = None):
        if num_shards <= 0:
            raise ValueError(f"num_shards must be positive, got {num_shards}")
        if cfg.max_ready % num_shards:
            raise ValueError(
                f"max_ready={cfg.max_ready} must divide evenly into "
                f"num_shards={num_shards} lane budgets")
        self.num_shards = num_shards
        self.lane_ready = cfg.max_ready // num_shards
        self.lane_batch = cfg.batch_size if lane_batch is None else int(lane_batch)
        if not 0 < self.lane_batch <= cfg.batch_size:
            raise ValueError(f"lane_batch must be in [1, {cfg.batch_size}], "
                             f"got {self.lane_batch}")
        if cfg.scan_len > 1 and self.lane_batch != cfg.batch_size:
            raise ValueError("scan_len > 1 needs the skew-proof lane_batch "
                             "== batch_size (overflow rounds are dispatched "
                             "per step, not scanned)")
        self.backend = backend if backend is not None else \
            platform.lanes_backend(num_shards)
        if self.backend not in LANE_BACKENDS:
            raise ValueError(f"backend must be one of {LANE_BACKENDS}, "
                             f"got {self.backend!r}")
        # the mesh must exist before super().__init__ constructs the state
        # through the _fresh_state hook
        self.mesh = make_lanes_mesh(num_shards) \
            if self.backend == "shard_map" else None
        super().__init__(packet_params, flow_params, cfg, config=config,
                         program=program)
        self._step_fn = jax.jit(self._sharded_step, donate_argnums=(0,))
        self._chunk_fn = jax.jit(self._sharded_chunk, donate_argnums=(0,))
        self._merge_fn = jax.jit(self._sharded_merge, donate_argnums=(0,))
        self._merge_warmed = False

    # ----------------------------------------------------------- lane plumbing
    def _fresh_state(self):
        """Stacked per-lane tracker banks (leading ``num_shards`` axis), each
        a full ``table_size`` table so slot numbering is shard-invariant.
        With ``cold_size > 0`` every lane also owns a private cold bank (the
        tiling maps over the whole two-level pytree) — spills and promotes
        stay lane-local, like every other piece of flow state.  Under
        shard_map the banks are pre-placed on the ``lanes`` axis so the
        carried state never reshards."""
        one = super()._fresh_state()
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.tile(a[None], (self.num_shards,) + (1,) * a.ndim), one)
        if self.mesh is not None:
            stacked = jax.device_put(
                stacked, shd.lanes_shardings(self.mesh, stacked))
        return stacked

    def _over_lanes(self, fn):
        """Map a per-lane function over the leading shard axis of every
        argument: ``vmap`` on single-device hosts, ``shard_map`` on the
        ``lanes`` mesh.  Under shard_map each device holds exactly one lane
        (local leading block of size 1), which is squeezed away so the lane
        body runs *unbatched* — its table updates stay dynamic-update-slices
        (in place) instead of vmap's batched scatters, which is where the
        per-device lanes win their throughput."""
        if self.backend == "vmap":
            return jax.vmap(fn)

        def body(*args):
            out = fn(*jax.tree_util.tree_map(lambda x: x[0], args))
            return jax.tree_util.tree_map(lambda x: x[None], out)

        # the lane body is the single-lane core, written without manual-axis
        # types: its cold-tier walks start their carries from constants, which
        # the varying-axes check would refuse against the lane-varying state
        spec = shd.lanes_spec()
        return jax.shard_map(body, mesh=self.mesh, in_specs=spec,
                             out_specs=spec, check_vma=False)

    def _merge_out(self, outs: PipelineStepOutput, src: jax.Array, *,
                   batch: Optional[int] = None) -> PipelineStepOutput:
        """Per-lane outputs (leading ``num_shards`` axis) -> one merged
        step output with the single-lane shapes: packet actions scattered
        back to original batch order (padding rows carry ``src ==
        batch_size`` and drop), lane drain rows concatenated into the global
        ``max_ready`` emission.  ``batch`` overrides the scatter target size
        for bucket-shaped masked steps (default: the config batch)."""
        B = self.cfg.batch_size if batch is None else batch
        flat = lambda a: a.reshape((self.cfg.max_ready,) + a.shape[2:])
        with jax.named_scope("lanes.merge"):
            pkt_actions = jnp.zeros((B,), jnp.int32).at[src.reshape(-1)].set(
                outs.pkt_actions.reshape(-1), mode="drop")
            return PipelineStepOutput(
                pkt_actions=pkt_actions,
                drained=jax.tree_util.tree_map(flat, outs.drained),
                flow_actions=flat(outs.flow_actions),
                flow_cls=flat(outs.flow_cls),
                flow_scores=flat(outs.flow_scores),
                **{k: getattr(outs, k).sum().astype(jnp.int32)
                   for k in COUNTERS},
            )

    # ------------------------------------------------------------ traced cores
    def _lanes_cond(self, make_lane, states, shards, keep):
        """Run ``make_lane(fallback)`` over every lane.  For the segmented
        tracker under vmap, the collision-fallback branch is hoisted out
        here: a vmapped ``lax.cond`` lowers to a select that runs the scan
        oracle on every batch, so instead ONE cond on "any lane collides"
        picks between the two statically-selected vmapped variants —
        collision-free batches (the common case) never touch the scan."""
        if self.cfg.tracker != "segmented" or self.backend != "vmap":
            return self._over_lanes(make_lane("auto"))(states, shards, keep)
        collides = jax.vmap(
            lambda p, k: fx.batch_collisions(p, self.cfg.table_size, k)
        )(shards, keep).any()
        return lax.cond(
            collides,
            lambda s, p, k: self._over_lanes(make_lane("always"))(s, p, k),
            lambda s, p, k: self._over_lanes(make_lane("never"))(s, p, k),
            states, shards, keep)

    def _sharded_core(self, states: ft.TrackerState, shards: ft.PacketBatch,
                      keep: jax.Array, src: jax.Array, *,
                      batch: Optional[int] = None
                      ) -> tuple[ft.TrackerState, PipelineStepOutput]:
        """One full sharded step: every lane runs the shard-shaped
        ``_lane_core`` (merge + lane-budget drain + both engines + decide)
        on its partition, then the lane outputs merge."""
        def make_lane(fb):
            return lambda st, p, k: self._lane_core(
                st, p, k, max_ready=self.lane_ready, fallback=fb)

        states, outs = self._lanes_cond(make_lane, states, shards, keep)
        return states, self._merge_out(outs, src, batch=batch)

    def _sharded_step(self, states, shards, keep, src):
        self.trace_count += 1  # python side effect: runs per trace, not per call
        return self._sharded_core(states, shards, keep, src)

    def _sharded_chunk(self, states, shards, keep, src):
        """``scan_len`` sharded steps in one dispatch (lockstep lanes only:
        every scanned step is a single round)."""
        self.trace_count += 1  # python side effect: runs per trace, not per call
        return lax.scan(lambda st, xs: self._sharded_core(st, *xs),
                        states, (shards, keep, src))

    def _masked_step(self, states, shards, keep, src):
        """Bucket-shaped sharded entry point: lane shapes are (S, bucket) —
        the masked dispatch always partitions at full bucket capacity (single
        round, skew-proof), so the merge scatter target is the bucket, read
        off the static lane shape."""
        self.trace_count += 1  # python side effect: runs per trace, not per call
        return self._sharded_core(states, shards, keep, src,
                                  batch=src.shape[1])

    def _sharded_merge(self, states, shards, keep):
        """Merge-only overflow round (step 2 + the per-packet engine): folds
        one spill window into every lane's bank without draining — the drain
        and flow engine run once per global batch, in the final round, so
        multi-round steps stay bit-exact to the oracle."""
        self.trace_count += 1  # python side effect: runs per trace, not per call

        def make_lane(fb):
            def lane(st, p, k):
                st, *counts = self._track(st, p, k, fallback=fb)
                return st, dict(zip(COUNTERS, counts)), self._decide_pkt(p)

            return lane

        return self._lanes_cond(make_lane, states, shards, keep)

    # -------------------------------------------------------------- host loop
    def _partition(self, packets: ft.PacketBatch) -> list[ShardedBatch]:
        lane_batch = None if self.lane_batch == self.cfg.batch_size \
            else self.lane_batch
        return partition_batch(packets, self.num_shards, lane_batch=lane_batch)

    def _lane_rows_max(self, packets: ft.PacketBatch,
                       keep: Optional[np.ndarray] = None) -> int:
        """Kept rows of the fullest lane, counted on the host from the
        tuple hashes the partition reads (no device readback)."""
        lane = shard_of(np.asarray(packets.tuple_hash), self.num_shards)
        if keep is not None:
            lane = lane[keep]
        return int(np.bincount(lane, minlength=self.num_shards).max())

    def _padded_rows(self, rounds: Sequence[ShardedBatch]) -> int:
        """Masked lane rows this step will dispatch.  Pure arithmetic —
        conservation guarantees the kept rows across all rounds are exactly
        the global batch, so no device readback is needed on the hot loop."""
        return (len(rounds) * self.num_shards * self.lane_batch
                - self.cfg.batch_size)

    def _dispatch_step(self, packets: ft.PacketBatch) -> InflightDispatch:
        """One global microbatch through all lanes, deferred-sync: the hash
        partition and EVERY round's enqueue (overflow merges + the fused
        drain step) happen now, without a single device readback — the old
        eager loop blocked on each merge round's counters mid-step.  The
        handle's ``wait`` blocks once, overlays the multi-round packet
        verdicts, applies feedback and records stats."""
        n = self._check_batch(packets)
        st = self.stats
        with span("octopus.partition", st) as part:
            rounds = self._partition(packets)
            fullest = self._lane_rows_max(packets)
        with span("octopus.enqueue", st) as enq:
            merge_outs = []
            for sb in rounds[:-1]:
                self.state, counts, acts = self._merge_fn(self.state,
                                                          sb.shards, sb.keep)
                merge_outs.append((sb, counts, acts))
            last = rounds[-1]
            self.state, out = self._step_fn(self.state, last.shards,
                                            last.keep, last.src)
            pack = host_pack(out)
        self._step_warmed = True
        # what the overlay reads of each round, in the pack's transfer: the
        # merge rounds' counters and verdicts, every round's lane rows
        earlier = [{**c, "pkt_actions": a, "keep": sb.keep, "src": sb.src}
                   for sb, c, a in merge_outs]
        if earlier:
            earlier.append({"keep": last.keep, "src": last.src})

        def readback(f: dict, got: list) -> list[tuple]:
            if got:  # overlay earlier rounds' packet verdicts
                with span("octopus.scatter", st):
                    merged = np.zeros((n,), np.int32)
                    for r in got[:-1]:
                        k = r["keep"]
                        merged[r["src"][k]] = r["pkt_actions"][k]
                    pos = got[-1]["src"][got[-1]["keep"]]
                    merged[pos] = f["pkt_actions"][pos]
                    f["pkt_actions"] = merged
            return [_feedback_args(f, tuple_hash=np.asarray(packets.tuple_hash))]

        def finish(host_extra_s: float) -> PipelineStepOutput:
            return self._complete(
                out, pack, readback, host_s=part.s + enq.s + host_extra_s,
                rounds=earlier, packets=n, dispatches=len(rounds),
                padded=self._padded_rows(rounds), lane_rows_max=fullest)

        return InflightDispatch(finish, steps=1, packets=n)

    def _dispatch_chunk(self, batches: Sequence[ft.PacketBatch]
                        ) -> InflightDispatch:
        """Exactly ``scan_len`` global microbatches enqueued as one device
        dispatch (``lax.scan`` over the fused sharded step — lockstep lanes,
        so every scanned step is one round); partition hashing happens now,
        feedback in the handle's ``wait``, in step order."""
        L = self.cfg.scan_len
        batches = list(batches)
        if len(batches) != L:
            raise ValueError(f"step_many needs exactly scan_len={L} "
                             f"microbatches, got {len(batches)}")
        if self.lane_batch != self.cfg.batch_size:
            # multi-round partitions cannot stack into one scanned dispatch
            # (overflow rounds would be dropped); the constructor pins
            # scan_len == 1 for this mode, so the chunk is a single step —
            # route it through the per-step dispatch, which enqueues every
            # round, and add the leading step axis on resolution
            inner = self._dispatch_step(batches[0])

            def finish(host_extra_s: float) -> PipelineStepOutput:
                inner.add_host_time(host_extra_s)
                out = inner.wait()  # records the dispatch in stats itself
                return jax.tree_util.tree_map(lambda a: a[None], out)

            return InflightDispatch(finish, steps=1,
                                    packets=self.cfg.batch_size)
        for b in batches:
            self._check_batch(b)
        st = self.stats
        with span("octopus.partition", st) as part:
            parts = [self._partition(b)[0] for b in batches]  # lockstep: 1 round
            fullest = sum(self._lane_rows_max(b) for b in batches)
        with span("octopus.enqueue", st) as enq:
            shards, keep, src = (
                jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *leaves)
                for leaves in zip(*parts))
            self.state, out = self._chunk_fn(self.state, shards, keep, src)
            pack = host_pack(out)
        n = L * self.cfg.batch_size
        # parts holds one single-round partition PER STEP — padding is per
        # step, not one multi-round step's worth
        padded = sum(self._padded_rows([p]) for p in parts)
        # the hashes come from the host-resident batches, one row per step
        hashes = np.stack([np.asarray(b.tuple_hash) for b in batches])

        def finish(host_extra_s: float) -> PipelineStepOutput:
            return self._complete(
                out, pack,
                lambda f, _: list(zip(*_feedback_args(f, tuple_hash=hashes))),
                host_s=part.s + enq.s + host_extra_s, packets=n, steps=L,
                padded=padded, lane_rows_max=fullest)

        return InflightDispatch(finish, steps=L, packets=n)

    def _zero_parts(self, bucket: Optional[int] = None) -> ShardedBatch:
        C = self.lane_batch if bucket is None else bucket
        S = self.num_shards
        B = self.cfg.batch_size if bucket is None else bucket
        pkt = jax.tree_util.tree_map(
            lambda a: jnp.zeros((S, C) + a.shape[1:], a.dtype),
            self._zero_batch())
        return ShardedBatch(shards=pkt, keep=jnp.zeros((S, C), bool),
                            src=jnp.full((S, C), B, jnp.int32))

    # ---------------------------------------------------- bucketed (masked)
    def warm_bucket(self, bucket: int) -> None:
        """Pre-compile the masked sharded entry for one bucket size: lane
        shapes (num_shards, bucket), single round."""
        if bucket <= 0:
            raise ValueError(f"bucket must be positive, got {bucket}")
        if bucket in self._warm_buckets:
            return
        scratch = self._fresh_state()
        zb = self._zero_parts(bucket)
        _, out = self._masked_fn(scratch, zb.shards, zb.keep, zb.src)
        jax.block_until_ready(host_pack(out))
        self._warm_buckets.add(bucket)

    def step_masked(self, packets: ft.PacketBatch,
                    keep: np.ndarray) -> PipelineStepOutput:
        """One padded request batch through all lanes.  The keep mask is
        folded into the hash partition (padding rows land in no lane), and
        the partition runs at full bucket capacity — always one round, so a
        bucket compiles exactly one entry whatever the skew.  The pack leaves
        the hashes out: the partition has read them to the host already."""
        bucket = np.shape(packets.ts)[0]
        k = np.asarray(keep, bool)
        if k.shape != (bucket,):
            raise ValueError(f"keep must have shape ({bucket},), got {k.shape}")
        n = int(k.sum())
        st = self.stats
        with span("octopus.step", st, dispatch=st.dispatches, bucket=bucket):
            with span("octopus.partition", st) as part:
                sb = partition_batch(packets, self.num_shards, keep=k)[0]
                fullest = self._lane_rows_max(packets, k)
            with span("octopus.enqueue", st) as enq:
                self.state, out = self._masked_fn(self.state, sb.shards,
                                                  sb.keep, sb.src)
                pack = host_pack(out)
            self._warm_buckets.add(bucket)
            hashes = np.asarray(packets.tuple_hash)
            return self._complete(
                out, pack, lambda f, _: [_feedback_args(f, k, hashes)],
                host_s=part.s + enq.s, packets=n,
                padded=self.num_shards * bucket - n, lane_rows_max=fullest)

    def warmup(self) -> None:
        """Compile the dispatch paths ``run`` will use on throwaway state:
        the chunked path when ``scan_len > 1``, else the fused step (plus the
        merge-only round when a smaller ``lane_batch`` makes overflow rounds
        possible)."""
        scratch = self._fresh_state()
        zb = self._zero_parts()
        if self.cfg.scan_len > 1:
            stacked = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (self.cfg.scan_len,) + a.shape),
                zb)
            _, out = self._chunk_fn(scratch, stacked.shards, stacked.keep,
                                    stacked.src)
            jax.block_until_ready(host_pack(out))
        else:
            if self.lane_batch < self.cfg.batch_size:
                scratch, *_ = self._merge_fn(scratch, zb.shards, zb.keep)
                self._merge_warmed = True
            _, out = self._step_fn(scratch, zb.shards, zb.keep, zb.src)
            jax.block_until_ready(host_pack(out))
            self._step_warmed = True

    def _warm_step(self) -> None:
        if self._step_warmed:
            return
        scratch = self._fresh_state()
        zb = self._zero_parts()
        if self.lane_batch < self.cfg.batch_size and not self._merge_warmed:
            scratch, *_ = self._merge_fn(scratch, zb.shards, zb.keep)
            self._merge_warmed = True
        _, out = self._step_fn(scratch, zb.shards, zb.keep, zb.src)
        jax.block_until_ready(host_pack(out))
        self._step_warmed = True

    # ------------------------------------------------------------- placement
    def plan(self) -> RoutePlan:
        """One RoutePlan across every lane's engines, each lane traced under
        its own ``lane<i>/`` scope (``plan().scoped("lane0")`` extracts one
        lane).  Shapes are per lane: the packet engine sees the lane capacity
        ``lane_batch``, the flow engine the lane drain budget."""
        use_pkt = self.cfg.pkt_head.needs_logits
        use_flow = self.cfg.flow_head.needs_logits

        def all_lanes(px: jax.Array, fx_: jax.Array):
            out = []
            for i in range(self.num_shards):
                with lane_scope(i):
                    if use_pkt:
                        with name_scope("pkt"):
                            out.append(self.packet_engine.fn(
                                self.packet_engine.params, px))
                    if use_flow:
                        with name_scope("flow"):
                            out.append(self.flow_engine.fn(
                                self.flow_engine.params, fx_))
            return out

        return RoutePlan.trace(
            all_lanes, self.packet_engine.abstract_input(self.lane_batch),
            self.flow_engine.abstract_input(self.lane_ready),
            config=self.runtime)

    def explain(self) -> str:
        """Placement report for the multi-lane step: the lane topology plus
        the composite per-lane plan."""
        plan = self.plan()
        c = self.cfg
        head = (f"ShardedOctopusPipeline: lanes={self.num_shards} "
                f"backend={self.backend} lane_batch={self.lane_batch} "
                f"lane_ready={self.lane_ready} batch={c.batch_size} "
                f"max_ready={c.max_ready} flow_model={c.flow_model} "
                f"table={c.table_size}x{self.num_shards} top_n={c.top_n} "
                f"tracker={c.tracker} scan_len={c.scan_len}")
        if c.cold_size:
            head += f" cold={c.cold_size}x{self.num_shards}({c.cold_policy})"
        head += f" heads={c.pkt_head.name}/{c.flow_head.name}"
        lines = [head, plan.explain()]
        for i in range(self.num_shards):
            sub = plan.scoped(f"lane{i}", strip=True)
            pkt = sub.scoped("pkt")
            flow = sub.scoped("flow")
            lines.append(f"  lane{i}: {len(pkt)} pkt + {len(flow)} flow "
                         f"matmuls, {sub.macs()} MACs")
        return "\n".join(lines)


__all__ = ["ShardedOctopusPipeline", "LANE_BACKENDS", "partition_batch",
           "shard_of"]
