"""The streaming in-network serving pipeline (paper §2.3 working procedure).

One continuous loop over packet microbatches — the paper's steps 1 -> 6 —
instead of the isolated per-call paths:

  1. parse        — ingest a :class:`PacketBatch` microbatch (the parser's
                    struct-of-arrays output; see ``repro.data.traffic``)
  2. track        — merge the batch into the hash-indexed flow table.  The
                    default tracker is the *segmented* update
                    (:func:`feature_extractor.segmented_update`): one
                    vectorized pass over the whole microbatch — sort by slot,
                    segment-reduce the feature lanes, rank-scatter the
                    series/payload memories — exactly how the paper's
                    extractor reaches 31 Mpkt/s by processing packets in
                    parallel.  In-batch slot collisions fall back to the
                    order-exact scan oracle per slot, so the result is always
                    bit-identical to ``tracker="scan"``
                    (:func:`flow_tracker.process_packets`, the FPGA's serial
                    semantics, kept as the differential reference).
  3. extract      — drain up to ``max_ready`` ready flows (count >= top_n)
                    from the table and recycle their slots
                    (:func:`flow_tracker.drain_ready`)
  4. infer        — per-packet metadata -> :class:`PacketEngine` (latency/VPE
                    side); emitted flow memories -> :class:`FlowEngine`
                    (throughput/AryPE side), both under the one runtime
                    config captured at construction
  5. decide       — logits -> allow/deny + class ids
  6. feed back    — decisions update the switch-facing rule table

Steps 2-5 compile into a single jit'd step whose :class:`TrackerState` is
donated — state flows across microbatches without copies.  All output shapes
are static (``batch_size`` packets in, ``max_ready`` masked flow rows out),
so the step is scan-friendly *and scanned*: with ``scan_len > 1`` the
pipeline dispatches ``scan_len`` microbatches per jit call (``lax.scan`` over
the fused step, donated carry, stacked drain outputs), amortizing the host
round-trip that otherwise dominates small-batch throughput.  Rule-table
feedback (step 6, host side) is then applied once per chunk, in step order —
decisions lag the wire by at most ``scan_len`` microbatches, the price of
dispatch amortization.  After warmup no call retraces (``trace_count`` stays
1; asserted in tests).

With ``overlap=True`` the loop goes one step further and stops serializing
host work with device work: ``step``/``step_many`` return an
:class:`InflightDispatch` handle immediately after *enqueueing* the jit call
(JAX dispatches asynchronously — the arrays come back as futures), and
``run`` becomes a double-buffered producer/consumer that stages chunk k+1
(batch pull, stacking, sharded ``partition_batch`` hashing) while chunk k
executes, waiting handles strictly in dispatch order.  Rule-table feedback
runs inside ``wait()`` — lagged by the one in-flight chunk but applied in
step order, so the run is bit-identical to the eager loop (differentially
tested).  :class:`PipelineStats` splits ``host_us`` vs ``device_us`` per
dispatch so the overlap is measured, not claimed: ``device_us`` is the
*exposed* device wait (what the host actually blocked on), which shrinks as
staging hides under execution.

Every host interval is a :class:`repro.runtime.span` (``octopus.pull``,
``.enqueue``, ``.wait``, ``.readback``, ``.feedback``, ``.counters``; the
served ``step_masked`` wraps them in ``octopus.step``), totalled per name in
``PipelineStats.spans`` and written to the profiler's trace when it runs.
What the host reads of a dispatch comes back in ONE device->host transfer:
the :class:`HostPack`, a flat int32 array enqueued right behind the step.
The step's device work runs under ``jax.named_scope`` names: ``track.promote``,
``track.merge`` (``track.fallback`` inside it), ``track.spill``,
``track.scrub``, ``drain``, ``engine.pkt`` and ``engine.flow``.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field
from typing import Any, Iterable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import cold_store
from repro.core import decisions
from repro.core import feature_extractor as fx
from repro.core import flow_tracker as ft
from repro.core.feature_extractor import packet_meta_features
from repro.kernels.flow_features.ops import default_program
from repro.models import paper_models
from repro.runtime import RoutePlan, RuntimeConfig, name_scope, resolve_config, span
from repro.serving.packet_path import FLOW_MODELS, FlowEngine, PacketEngine

TRACKERS = ("segmented", "scan")


@dataclass(frozen=True)
class PipelineConfig:
    """Static shapes + thresholds of the streaming loop (jit cache keys)."""

    batch_size: int = 32  # packets per microbatch (step granularity)
    max_ready: int = 8  # ready-flow rows drained per step
    flow_model: str = "cnn"  # "cnn" | "transformer"
    table_size: int = 1024  # flow-state table depth (paper: 8192)
    top_n: int = paper_models.CNN_SEQ  # ready threshold / series depth
    top_k: int = paper_models.TF_PKTS  # payload rows per flow
    pay_bytes: int = paper_models.TF_BYTES  # payload bytes per packet
    tracker: str = "segmented"  # "segmented" (vectorized) | "scan" (oracle)
    scan_len: int = 1  # microbatches fused per dispatch (lax.scan length)
    overlap: bool = False  # deferred-sync dispatch: step/step_many return an
    # InflightDispatch handle; run() double-buffers over it
    cold_size: int = 0  # second-level (cold) flow table slots; 0 disables
    cold_policy: str = "age"  # cold eviction policy: "age" | "lru"
    deny_threshold: float = 0.5  # default BinaryHead packet-deny threshold
    pkt_head: Optional[Any] = None  # packet DecisionHead (None -> BinaryHead)
    flow_head: Optional[Any] = None  # flow DecisionHead (None -> ClassHead)

    def __post_init__(self):
        # resolve the default heads here (not in the pipeline) so the frozen
        # config compares/hashes by the heads it will actually run with, and
        # deny_threshold reaches the default head exactly once
        if self.pkt_head is None:
            object.__setattr__(self, "pkt_head",
                               decisions.BinaryHead(self.deny_threshold))
        if self.flow_head is None:
            object.__setattr__(self, "flow_head", decisions.ClassHead())
        for role, head in (("pkt_head", self.pkt_head),
                           ("flow_head", self.flow_head)):
            if not isinstance(head, decisions.DecisionHead):
                raise ValueError(f"{role} must implement DecisionHead "
                                 f"(name + needs_logits), got {head!r}")
        if self.flow_model not in FLOW_MODELS:
            raise ValueError(f"flow_model must be one of {FLOW_MODELS}, "
                             f"got {self.flow_model!r}")
        if self.tracker not in TRACKERS:
            raise ValueError(f"tracker must be one of {TRACKERS}, "
                             f"got {self.tracker!r}")
        if self.batch_size <= 0 or not 0 < self.max_ready <= self.table_size:
            raise ValueError("batch_size and max_ready must be positive "
                             "(max_ready <= table_size)")
        if self.scan_len <= 0:
            raise ValueError(f"scan_len must be positive, got {self.scan_len}")
        if self.cold_size < 0:
            raise ValueError(f"cold_size must be >= 0, got {self.cold_size}")
        if self.cold_policy not in cold_store.COLD_POLICIES:
            raise ValueError(f"cold_policy must be one of "
                             f"{cold_store.COLD_POLICIES}, "
                             f"got {self.cold_policy!r}")
        # the flow engine consumes the tracker memories directly — their
        # depths must match the model's fixed input geometry.  A feature-only
        # flow head never runs the engine, so the tracker geometry is free
        # (heavy-hitter configs shrink top_n to tune the drain threshold).
        if not self.flow_head.needs_logits:
            return
        if self.flow_model == "cnn" and self.top_n != paper_models.CNN_SEQ:
            raise ValueError(f"cnn flow model needs top_n == {paper_models.CNN_SEQ} "
                             f"(got {self.top_n})")
        if self.flow_model == "transformer" and (
                self.top_k != paper_models.TF_PKTS
                or self.pay_bytes != paper_models.TF_BYTES):
            raise ValueError(
                f"transformer flow model needs top_k == {paper_models.TF_PKTS} and "
                f"pay_bytes == {paper_models.TF_BYTES} "
                f"(got {self.top_k}/{self.pay_bytes})")


class PipelineStepOutput(NamedTuple):
    """Device-side outputs of one fused step (static shapes).  Chunked
    dispatch (``step_many``) returns the same tuple with a leading
    ``scan_len`` axis on every leaf."""

    pkt_actions: jax.Array  # (batch_size,) int32 0 allow / 1 deny
    drained: ft.DrainResult  # max_ready rows + mask
    flow_actions: jax.Array  # (max_ready,) int32
    flow_cls: jax.Array  # (max_ready,) int32
    flow_scores: jax.Array  # (max_ready,) float32 — the flow head's score
    new_flows: jax.Array  # () int32 — flows established this step
    evicted: jax.Array  # () int32 — stale flows recycled by collision
    spilled: jax.Array  # () int32 — evictions spilled into the cold store
    promoted: jax.Array  # () int32 — cold entries promoted back into hot
    cold_walk: jax.Array  # () int32 — serial trips of the cold tier's
    # promote and spill walks (0 without a cold tier)
    fallback_slots: jax.Array  # () int32 — table slots whose in-batch
    # collision took the segmented tracker's scan fallback
    ready_left: jax.Array  # () int32 — ready flows left undrained (max_ready)


# the step counters, read back in the host pack with step 6's arguments
COUNTERS = ("new_flows", "evicted", "spilled", "promoted", "cold_walk",
            "fallback_slots", "ready_left")


class HostPack(NamedTuple):
    """What the host reads of one dispatch, as ONE flat int32 device array
    (``flat``), and where each field lies in it: ``layout`` maps a field's
    name to its offset, shape and dtype, static from the outputs' shapes."""

    flat: jax.Array
    layout: dict

    def take(self, host: np.ndarray, name: str) -> np.ndarray:
        """Field ``name`` of the pack's host copy ``host`` in its own dtype:
        a view for 4-byte dtypes, a copy for narrower ones (the bool mask)."""
        at, shape, dtype = self.layout[name]
        part = host[at:at + math.prod(shape)].reshape(shape)
        return part.view(dtype) if dtype.itemsize == 4 else part.astype(dtype)


@jax.jit
def _flatten(leaves: tuple) -> jax.Array:
    # 4-byte leaves bit for bit, narrower ones (the bool mask) widened
    return jnp.concatenate([
        (lax.bitcast_convert_type(a, jnp.int32) if a.dtype.itemsize == 4
         else a.astype(jnp.int32)).ravel() for a in leaves])


def host_pack(out: PipelineStepOutput,
              tuple_hash: Optional[jax.Array] = None) -> HostPack:
    """Enqueue the host pack of one dispatch: ``pkt_actions``, the drained
    ``mask`` and ``tuple_id``, ``flow_actions``, ``flow_cls`` and the
    ``COUNTERS``, plus the batch's ``tuple_hash`` where the caller echoes it
    (the single-lane steps: the served batch is a device upload).
    ``flow_scores`` and the rest of ``drained`` stay on the device.  A
    program of its own, built from whatever the step returned, so the pack
    always holds exactly the outputs the step handed back."""
    d = out.drained
    leaves = {"pkt_actions": out.pkt_actions, "mask": d.mask,
              "tuple_id": d.tuple_id, "flow_actions": out.flow_actions,
              "flow_cls": out.flow_cls,
              **{k: getattr(out, k) for k in COUNTERS}}
    if tuple_hash is not None:
        leaves["tuple_hash"] = tuple_hash
    layout, at = {}, 0
    for name, a in leaves.items():
        layout[name] = (at, tuple(a.shape), np.dtype(a.dtype))
        at += math.prod(a.shape)
    return HostPack(_flatten(tuple(leaves.values())), layout)


def _feedback_args(fields: dict, keep: Optional[np.ndarray] = None,
                   tuple_hash: Optional[np.ndarray] = None) -> tuple:
    """:meth:`OctopusPipeline._feedback`'s arguments from one dispatch's
    host fields: the batch's hashes from the pack unless the caller holds
    them (``tuple_hash``); of a padded bucket, the kept rows only."""
    th = fields["tuple_hash"] if tuple_hash is None else tuple_hash
    acts = fields["pkt_actions"]
    if keep is not None:
        th, acts = th[keep], acts[keep]
    return (th, acts, fields["mask"], fields["tuple_id"],
            fields["flow_actions"], fields["flow_cls"])


class LatencyReservoir:
    """Bounded ring-buffer sample for percentile latency reporting.

    ``record_dispatch`` / the serving frontend feed every observed latency
    in; only the most recent ``capacity`` samples are retained, so p50/p99
    stay computable over an unbounded run without unbounded memory (the
    paper's dataplane equivalent: a fixed histogram SRAM, not a packet log).
    Idle reservoirs report ``nan`` — the ``PathStats.latency_us`` convention
    (0.0 would read as an impossibly fast path)."""

    __slots__ = ("capacity", "_buf", "_n")

    def __init__(self, capacity: int = 1024):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        self._buf = np.empty(capacity, np.float64)
        self._n = 0  # total added; the ring holds the last min(n, capacity)

    def add(self, value: float) -> None:
        self._buf[self._n % self.capacity] = value
        self._n += 1

    def __len__(self) -> int:
        return min(self._n, self.capacity)

    @property
    def total_added(self) -> int:
        return self._n

    def percentile(self, q: float) -> float:
        """q-th percentile (0-100) of the retained sample; ``nan`` when
        nothing was recorded yet."""
        if self._n == 0:
            return float("nan")
        return float(np.percentile(self._buf[: len(self)], q))

    @property
    def p50(self) -> float:
        return self.percentile(50.0)

    @property
    def p99(self) -> float:
        return self.percentile(99.0)


@dataclass
class PipelineStats:
    """Sustained-loop counters, shared by the single-lane and sharded
    pipelines.  All mutation goes through :meth:`record_dispatch`, which
    counts per *actual* device dispatch: ``packets`` is the number of real
    packets ingested (a sharded dispatch also moves ``padded`` masked lane
    rows — those are deliberately not packets, so ``pkt_per_s`` stays an
    honest wire-rate), ``steps`` is pipeline steps (a chunked dispatch
    advances ``scan_len`` of them), ``dispatches`` is host->device round
    trips (a multi-round sharded step makes several).  A sharded
    dispatch also adds ``lane_rows_max``, the kept rows of its fullest lane:
    ``packets / (packets + padded)`` is the lanes' fill and ``num_shards *
    lane_rows_max / packets`` their skew (1.0 when the lanes are even).

    Beyond the aggregate means (``dispatch_us``/``step_us``), every timed
    dispatch region also lands one sample in a bounded
    :class:`LatencyReservoir`, so tail latency (``p50_us``/``p99_us``) is
    reportable over unbounded runs — idle stats report ``nan``.  ``spans``
    totals every host span by name (:class:`repro.runtime.SpanTotal`)."""

    steps: int = 0
    total_s: float = 0.0
    packets: int = 0
    flows: int = 0  # ready flows emitted + classified
    new_flows: int = 0
    evicted: int = 0
    spilled: int = 0  # evictions captured by the cold store (cold_size > 0)
    promoted: int = 0  # cold entries re-established into hot
    cold_walk: int = 0  # serial trips of the cold tier's two walks
    dispatches: int = 0  # host->device round-trips (chunking lowers it below
    # steps; sharded overflow rounds raise it above)
    padded: int = 0  # dispatched-but-masked lane rows (sharding skew cost)
    lane_rows_max: int = 0  # the fullest lane's kept rows, summed over
    # dispatches (sharded pipelines; host-side, from the hash partition)
    fallback_dispatches: int = 0  # dispatches whose merge took the fallback
    fallback_slots: int = 0  # table slots that took it, summed
    ready_left: int = 0  # ready flows left undrained after each drain, summed
    host_reads: int = 0  # explicit device->host reads of step outputs: one
    # per completed dispatch (the host pack), whatever its rounds
    host_s: float = 0.0  # host-side share: pull, enqueue (and partition),
    # readback and feedback spans — not the counters' slicing
    device_s: float = 0.0  # EXPOSED device wait (the ``octopus.wait`` span) —
    # what the host blocked on, not raw execution time; overlap shrinks it
    lat: LatencyReservoir = field(default_factory=LatencyReservoir)
    spans: dict = field(default_factory=dict)  # name -> SpanTotal

    def record_dispatch(self, dt: float, *, packets: int, steps: int = 1,
                        dispatches: int = 1, flows: int = 0,
                        new_flows: int = 0, evicted: int = 0,
                        spilled: int = 0, promoted: int = 0,
                        cold_walk: int = 0, fallback_slots: int = 0,
                        ready_left: int = 0, padded: int = 0,
                        lane_rows_max: int = 0, host_reads: int = 0,
                        host_s: float = 0.0, device_s: float = 0.0) -> None:
        """Fold one timed dispatch (or fused multi-step chunk) into the
        counters.  ``packets`` must be the real packet count — callers that
        dispatch padded lanes pass the keep-mask total, not the lane shape.
        ``host_s``/``device_s`` split ``dt`` into host work vs exposed device
        wait; callers that don't measure the split leave them 0 (the totals
        stay correct, only the attribution is unknown)."""
        self.total_s += dt
        self.packets += packets
        self.steps += steps
        self.dispatches += dispatches
        self.flows += flows
        self.new_flows += new_flows
        self.evicted += evicted
        self.spilled += spilled
        self.promoted += promoted
        self.cold_walk += cold_walk
        self.fallback_dispatches += fallback_slots > 0
        self.fallback_slots += fallback_slots
        self.ready_left += ready_left
        self.padded += padded
        self.lane_rows_max += lane_rows_max
        self.host_reads += host_reads
        self.host_s += host_s
        self.device_s += device_s
        self.lat.add(dt * 1e6)  # one sample per timed region (us)

    @property
    def pkt_per_s(self) -> float:
        return self.packets / self.total_s if self.total_s > 0 else 0.0

    @property
    def flow_per_s(self) -> float:
        return self.flows / self.total_s if self.total_s > 0 else 0.0

    @property
    def step_us(self) -> float:
        return self.total_s / self.steps * 1e6 if self.steps else float("nan")

    @property
    def dispatch_us(self) -> float:
        """Wall time per host->device round trip — the latency the chunked /
        sharded dispatch modes actually amortize (``step_us`` divides by
        pipeline steps, which a fused chunk advances several at a time)."""
        return self.total_s / self.dispatches * 1e6 if self.dispatches else float("nan")

    @property
    def host_us(self) -> float:
        """Mean host-side time per dispatch: staging + enqueue + read-back +
        rule-table feedback (+ the producer pull when driven by ``run``)."""
        return self.host_s / self.dispatches * 1e6 if self.dispatches else float("nan")

    @property
    def device_us(self) -> float:
        """Mean *exposed* device wait per dispatch — the block the host
        could not hide.  Under ``overlap`` this drops below the raw device
        time because staging for the next chunk runs during execution."""
        return self.device_s / self.dispatches * 1e6 if self.dispatches else float("nan")

    @property
    def p50_us(self) -> float:
        """Median timed-dispatch wall time (``nan`` when idle)."""
        return self.lat.p50

    @property
    def p99_us(self) -> float:
        """99th-percentile timed-dispatch wall time (``nan`` when idle) —
        the bounded-tail claim the serving frontend is measured against."""
        return self.lat.p99


class InflightDispatch:
    """Handle for one deferred-sync dispatch (``PipelineConfig.overlap``).

    The device work is already *enqueued* when the handle exists (JAX async
    dispatch returned future arrays); nothing has been blocked on.
    :meth:`wait` blocks on the outputs, applies the rule-table feedback
    (step 6) and folds the dispatch into the pipeline stats — exactly what
    the eager path does inline.  Because the rule table never feeds into the
    device computation, a sequence of handles waited **in dispatch order**
    is bit-identical to the eager loop: feedback lags the wire by at most
    the in-flight dispatch, but lands in the same step order.

    ``wait`` is idempotent — the first call resolves and caches the
    :class:`PipelineStepOutput`, later calls return it (the dispatch is
    recorded in stats exactly once).  :meth:`add_host_time` attributes host
    work done on this dispatch's behalf while a previous one was in flight
    (the double-buffered ``run`` loop charges the batch pull here)."""

    __slots__ = ("steps", "packets", "_finish", "_host_extra_s", "_out")

    def __init__(self, finish, *, steps: int, packets: int):
        self._finish = finish  # closure(host_extra_s) -> PipelineStepOutput
        self.steps = steps  # pipeline steps this dispatch advances
        self.packets = packets  # real packets it carries
        self._host_extra_s = 0.0
        self._out: Optional[PipelineStepOutput] = None

    @property
    def done(self) -> bool:
        """True once :meth:`wait` has resolved this handle."""
        return self._out is not None

    def add_host_time(self, dt_s: float) -> None:
        """Charge host time spent on this dispatch's behalf (producer pull,
        staging) to its stats record.  No effect after :meth:`wait`."""
        self._host_extra_s += dt_s

    def wait(self) -> PipelineStepOutput:
        """Block until the device outputs are ready, apply feedback, record
        stats; return the step output.  Idempotent."""
        if self._out is None:
            self._out = self._finish(self._host_extra_s)
            self._finish = None  # drop the closure (it captures device refs)
        return self._out


class OctopusPipeline:
    """Streaming serving loop composing the tracker and both inference
    engines under one :class:`RuntimeConfig` (captured at construction, like
    the standalone paths — jit caches by shapes, not ambient context).

    ``run(traffic, steps=N)`` sustains :class:`TrackerState` across
    microbatches; the state argument is donated to the jit'd step, so the
    table updates in place instead of round-tripping fresh buffers.  With
    ``cfg.scan_len > 1`` the loop pulls ``scan_len`` microbatches at a time
    and dispatches them as one ``lax.scan`` over the fused step
    (:meth:`step_many`); a final partial chunk falls back to per-step
    dispatch (which compiles the single-step path separately)."""

    def __init__(self, packet_params: Any, flow_params: Any,
                 cfg: PipelineConfig = PipelineConfig(), *,
                 config: Optional[RuntimeConfig] = None,
                 program: Optional[jax.Array] = None):
        self.cfg = cfg
        self.runtime = resolve_config(config)
        self.packet_engine = PacketEngine(packet_params, config=self.runtime)
        self.flow_engine = FlowEngine(flow_params, cfg.flow_model,
                                      config=self.runtime)
        self.program = program if program is not None else default_program()
        if cfg.tracker == "segmented" and not self.runtime.use_pallas:
            fx.check_default_program(self.program)  # fail at construction
        self.rules = decisions.RuleTable()  # the switch-facing table (step 6)
        self.stats = PipelineStats()
        self.state = self._fresh_state()
        self.trace_count = 0  # bumps only when a jit entry point re-traces
        self._step_warmed = False
        self._step_fn = jax.jit(self._step, donate_argnums=(0,))
        self._chunk_fn = jax.jit(self._chunk, donate_argnums=(0,))
        self._masked_fn = jax.jit(self._masked_step, donate_argnums=(0,))
        self._warm_buckets: set[int] = set()  # bucket sizes compiled so far

    # ------------------------------------------------------------ traced core
    def _fresh_state(self):
        """State factory shared by construction, warmup scratch and reset —
        overridable (the sharded pipeline stacks per-lane banks here).
        Returns a plain :class:`~repro.core.flow_tracker.TrackerState` in
        hot-only mode (``cold_size == 0`` — byte-identical to the
        single-level pipeline), a :class:`~repro.core.cold_store.TwoLevelState`
        with the cold table attached otherwise."""
        hot = ft.init_state(self.cfg.table_size, self.cfg.top_n,
                            self.cfg.top_k, self.cfg.pay_bytes)
        if not self.cfg.cold_size:
            return hot
        return cold_store.TwoLevelState(
            hot=hot, cold=cold_store.init_cold(
                self.cfg.cold_size, self.cfg.top_n, self.cfg.top_k,
                self.cfg.pay_bytes))

    def _merge(self, hot: ft.TrackerState, packets: ft.PacketBatch,
               keep: Optional[jax.Array], *, fallback: str,
               with_spills: bool = False):
        """The raw tracker merge under ``cfg.tracker``: returns
        ``(hot, new, evicted, fallback_slots)`` (plus the spill records when
        asked).  The scan tracker is the serial oracle: it never falls back."""
        if self.cfg.tracker == "segmented":
            out = fx.segmented_update(
                hot, packets, self.program, top_n=self.cfg.top_n,
                use_pallas=self.runtime.use_pallas,
                interpret=self.runtime.interpret, keep=keep,
                fallback=fallback, with_spills=with_spills)
            hot, seg = out[:2]
            return (hot, seg.new_flows, seg.evicted, seg.fallback_slots,
                    *out[2:])
        out = ft.process_packets(hot, packets, self.program,
                                 top_n=self.cfg.top_n, keep=keep,
                                 with_spills=with_spills)
        hot, outs = out[:2]
        return (hot, outs.new_flow.sum().astype(jnp.int32),
                outs.evicted.sum().astype(jnp.int32), jnp.int32(0), *out[2:])

    def _track(self, state, packets: ft.PacketBatch,
               keep: Optional[jax.Array] = None, *, fallback: str = "auto"):
        """Step 2 only: merge one (optionally masked) microbatch into the
        tracker under ``cfg.tracker``.  Returns ``(state, new_flows,
        evicted, spilled, promoted, cold_walk, fallback_slots)`` — the
        merge half of the lane contract, its counters in ``COUNTERS`` order,
        dispatched on its own by the sharded pipeline's overflow rounds.
        ``fallback`` is forwarded to the segmented tracker's collision
        branch (vmapped callers hoist it).

        In hot-only mode the state is a plain tracker bank, spills, promotes
        and cold-walk trips are constant zero, and the traced merge is
        identical to the single-level pipeline.  With ``cold_size > 0`` the
        two-level step semantics documented in :mod:`repro.core.cold_store`
        run around the same merge: promote -> merge (with spill records) ->
        spill -> scrub."""
        zero = jnp.int32(0)
        if not self.cfg.cold_size:
            with jax.named_scope("track.merge"):
                state, new, ev, fb = self._merge(state, packets, keep,
                                                 fallback=fallback)
            return state, new, ev, zero, zero, zero, fb
        hot, cold = state.hot, state.cold
        with jax.named_scope("track.promote"):
            hot, cold, promoted, walked = cold_store.promote_pass(
                hot, cold, packets, keep, policy=self.cfg.cold_policy)
        with jax.named_scope("track.merge"):
            hot, new, ev, fb, spills = self._merge(hot, packets, keep,
                                                   fallback=fallback,
                                                   with_spills=True)
        with jax.named_scope("track.spill"):
            cold, spilled = cold_store.apply_spills(
                cold, spills, policy=self.cfg.cold_policy)
        with jax.named_scope("track.scrub"):
            cold = cold_store.scrub_live(cold, hot, packets, keep)
        return (cold_store.TwoLevelState(hot, cold), new, ev, spilled,
                promoted, walked + spilled, fb)

    def _lane_core(self, state, packets: ft.PacketBatch,
                   keep: Optional[jax.Array] = None, *,
                   max_ready: Optional[int] = None, fallback: str = "auto"
                   ) -> tuple[Any, PipelineStepOutput]:
        """Steps 2-5 for ONE lane, the shard-shaped step contract: merge the
        (optionally keep-masked) packets, drain up to ``max_ready`` ready
        flows (the global budget, or one lane's split of it), run both
        engines, decide.  The single-lane pipeline calls it with the full
        batch and budget; the sharded pipeline vmaps / shard_maps it across
        hash-partitioned lanes.  Draining always happens on the hot bank —
        cold flows re-enter the hot table through promotion before they can
        emit."""
        state, *counts = self._track(state, packets, keep, fallback=fallback)
        hot = state.hot if self.cfg.cold_size else state
        with jax.named_scope("drain"):
            ready = ft.ready_mask(hot, top_n=self.cfg.top_n).sum()
            hot, drained = ft.drain_ready(
                hot, top_n=self.cfg.top_n,
                max_ready=self.cfg.max_ready if max_ready is None else max_ready)
            ready_left = (ready - drained.mask.sum()).astype(jnp.int32)
        state = state._replace(hot=hot) if self.cfg.cold_size else hot
        pkt_actions = self._decide_pkt(packets)
        flow_actions, flow_cls, flow_scores = self._decide_flow(drained)
        return state, PipelineStepOutput(
            pkt_actions=pkt_actions,
            drained=drained,
            flow_actions=flow_actions,
            flow_cls=flow_cls,
            flow_scores=flow_scores,
            **dict(zip(COUNTERS, counts)),
            ready_left=ready_left,
        )

    # ------------------------------------------------------------ decide (5)
    def _decide_pkt(self, packets: ft.PacketBatch) -> jax.Array:
        """Step 4+5, packet side: run the packet engine only when the head
        consumes logits (feature-only heads skip the inference entirely),
        then let the head decide."""
        head = self.cfg.pkt_head
        logits = None
        if head.needs_logits:
            with jax.named_scope("engine.pkt"):
                logits = self.packet_engine.fn(self.packet_engine.params,
                                               packet_meta_features(packets))
        return head.decide(logits, packets)

    def _decide_flow(self, drained: ft.DrainResult
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Step 4+5, flow side: prep + flow-engine inference only for
        logits-consuming heads, then the head maps (logits, drained rows) to
        (actions, classes, scores)."""
        head = self.cfg.flow_head
        logits = None
        if head.needs_logits:
            with jax.named_scope("engine.flow"):
                flow_x = self.flow_engine.prep(drained.series, drained.payload)
                logits = self.flow_engine.fn(self.flow_engine.params, flow_x)
        return head.decide(logits, drained)

    def _decide(self, packets: ft.PacketBatch, drained: ft.DrainResult
                ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Both decide halves at once — the full step-5 extension point."""
        return (self._decide_pkt(packets),) + self._decide_flow(drained)

    def _step_core(self, state: ft.TrackerState,
                   packets: ft.PacketBatch) -> tuple[ft.TrackerState,
                                                     PipelineStepOutput]:
        """Steps 2-5 as one traced function (no trace counting — both jit
        entry points share it): the lane core at full batch + budget."""
        return self._lane_core(state, packets)

    def _step(self, state: ft.TrackerState,
              packets: ft.PacketBatch) -> tuple[ft.TrackerState, PipelineStepOutput]:
        self.trace_count += 1  # python side effect: runs per trace, not per call
        return self._step_core(state, packets)

    def _chunk(self, state: ft.TrackerState,
               stacked: ft.PacketBatch) -> tuple[ft.TrackerState, PipelineStepOutput]:
        """``scan_len`` fused steps in one dispatch: ``lax.scan`` over
        :meth:`_step_core` with the tracker state as carry.  Outputs come
        back stacked with a leading ``scan_len`` axis."""
        self.trace_count += 1  # python side effect: runs per trace, not per call
        return lax.scan(self._step_core, state, stacked)

    def _masked_step(self, state: ft.TrackerState, packets: ft.PacketBatch,
                     keep: jax.Array) -> tuple[ft.TrackerState,
                                               PipelineStepOutput]:
        """The serving frontend's bucket-shaped entry point: the full lane
        core over a *padded* microbatch whose tail rows carry ``keep ==
        False`` (the trackers drop them via the keep mask, so the state is
        bit-identical to merging only the kept rows).  jit caches one
        compiled entry per bucket shape — ``warm_bucket`` pre-compiles them
        so ragged arrivals never retrace."""
        self.trace_count += 1  # python side effect: runs per trace, not per call
        return self._lane_core(state, packets, keep)

    # -------------------------------------------------------------- host loop
    def warmup(self) -> None:
        """Compile the dispatch path ``run`` will use, on a throwaway state
        (the live table is untouched).  Compiles the chunked path when
        ``scan_len > 1``, else the single-step path; if a ``run`` later hits
        a partial final chunk, the single-step path is warmed on the spot —
        outside the timed region, so stats never include compilation."""
        scratch = self._fresh_state()
        if self.cfg.scan_len > 1:
            stacked = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (self.cfg.scan_len,) + a.shape),
                self._zero_batch())
            _, out = self._chunk_fn(scratch, stacked)
            jax.block_until_ready(host_pack(out, stacked.tuple_hash))
        else:
            self._warm_step()

    def _warm_step(self) -> None:
        """Compile the single-step path on scratch state (idempotent) so a
        partial-chunk fallback never pays compilation inside ``step``'s
        timing window."""
        if self._step_warmed:
            return
        scratch = self._fresh_state()
        batch = self._zero_batch()
        _, out = self._step_fn(scratch, batch)
        jax.block_until_ready(host_pack(out, batch.tuple_hash))
        self._step_warmed = True

    def _zero_batch(self, n: Optional[int] = None) -> ft.PacketBatch:
        p, c = self.cfg.batch_size if n is None else n, self.cfg
        return ft.PacketBatch(
            ts=jnp.zeros((p,), jnp.int32), size=jnp.zeros((p,), jnp.int32),
            dir=jnp.zeros((p,), jnp.int32), flags=jnp.zeros((p,), jnp.int32),
            proto=jnp.zeros((p,), jnp.int32),
            tuple_hash=jnp.zeros((p,), jnp.int32),
            payload=jnp.zeros((p, c.pay_bytes), jnp.int32))

    def _check_batch(self, packets: ft.PacketBatch) -> int:
        n = int(packets.ts.shape[0])
        if n != self.cfg.batch_size:
            raise ValueError(f"microbatch must have batch_size="
                             f"{self.cfg.batch_size} packets, got {n}")
        return n

    def _feedback(self, tuple_hash: np.ndarray, pkt_actions: np.ndarray,
                  mask: np.ndarray, tuple_id: np.ndarray,
                  flow_actions: np.ndarray, flow_cls: np.ndarray) -> int:
        """Step 6 for one microbatch: decisions -> the switch-facing rule
        table.  Returns the number of emitted flows."""
        self.rules.update(tuple_hash, pkt_actions)
        n_flows = int(mask.sum())
        if n_flows:
            self.rules.update(tuple_id[mask], flow_actions[mask],
                              flow_cls[mask])
        return n_flows

    def _complete(self, out: PipelineStepOutput, pack: HostPack, rows, *,
                  host_s: float, rounds: Sequence[dict] = (),
                  **record) -> PipelineStepOutput:
        """The host's half of one enqueued dispatch, after the enqueue: wait
        for ``out``, then read the host ``pack`` and the outputs of earlier
        merge-only ``rounds`` (sharded overflow) back in ONE transfer, slice
        step 6's arguments (``rows(fields, rounds)`` returns one
        :meth:`_feedback` argument tuple per step), feed the rule table,
        slice the counters and record the dispatch.  ``host_s`` is the host
        time already spent on it (pull, partition, enqueue); the counters'
        slicing is timed apart from it.  Returns ``out`` with its host-read
        fields replaced by numpy views of that one host copy, the counters
        summed over rounds."""
        st = self.stats
        with span("octopus.wait", st) as wait:
            jax.block_until_ready(out)
        with span("octopus.readback", st) as rb:
            host, rounds = jax.device_get((pack.flat, list(rounds)))
            fields = {k: pack.take(host, k) for k in pack.layout
                      if k not in COUNTERS}
            steps = rows(fields, rounds)
        with span("octopus.feedback", st) as fb:
            flows = sum(self._feedback(*args) for args in steps)
        with span("octopus.counters", st):
            for k in COUNTERS:
                fields[k] = np.asarray(sum((c[k].sum(dtype=np.int32)
                                            for c in rounds if k in c),
                                           pack.take(host, k)))
            counters = {k: int(fields[k].sum()) for k in COUNTERS}
        host_s += rb.s + fb.s
        st.record_dispatch(host_s + wait.s, flows=flows, host_s=host_s,
                           device_s=wait.s, host_reads=1, **counters,
                           **record)
        return out._replace(
            pkt_actions=fields["pkt_actions"],
            drained=out.drained._replace(mask=fields["mask"],
                                         tuple_id=fields["tuple_id"]),
            flow_actions=fields["flow_actions"], flow_cls=fields["flow_cls"],
            **{k: fields[k] for k in COUNTERS})

    def _dispatch_step(self, packets: ft.PacketBatch) -> InflightDispatch:
        """Enqueue one microbatch (steps 2-5) without blocking — JAX async
        dispatch hands the outputs back as future arrays, so the host is
        free to stage the next chunk while this one executes.  The returned
        handle's ``wait`` blocks, applies feedback and records stats."""
        n = self._check_batch(packets)
        with span("octopus.enqueue", self.stats) as enq:
            self.state, out = self._step_fn(self.state, packets)
            pack = host_pack(out, packets.tuple_hash)
        self._step_warmed = True  # compiled now, whatever the entry path

        def finish(host_extra_s: float) -> PipelineStepOutput:
            # block on the outputs only: under overlap the state has already
            # been donated to the next enqueued dispatch (same computation,
            # so `out` ready implies the state update finished too)
            return self._complete(out, pack,
                                  lambda f, _: [_feedback_args(f)],
                                  host_s=enq.s + host_extra_s, packets=n)

        return InflightDispatch(finish, steps=1, packets=n)

    def step(self, packets: ft.PacketBatch):
        """Run one microbatch through the loop and fold the decisions into
        the rule table.  ``packets`` must have ``batch_size`` rows (static
        shape — a different size would recompile).

        Returns the :class:`PipelineStepOutput` eagerly, or — with
        ``cfg.overlap`` — an :class:`InflightDispatch` that the caller waits
        in dispatch order (feedback is then lagged by the one in-flight
        dispatch, still bit-identical; see the class docstring)."""
        h = self._dispatch_step(packets)
        return h if self.cfg.overlap else h.wait()

    # ---------------------------------------------------- bucketed (masked)
    def warm_bucket(self, bucket: int) -> None:
        """Pre-compile the masked entry point for one bucket size on
        throwaway state (idempotent per size).  The serving frontend calls
        this for every configured bucket at startup, so ragged request sizes
        pad to a pre-warmed shape and ``trace_count`` stays flat."""
        if bucket <= 0:
            raise ValueError(f"bucket must be positive, got {bucket}")
        if bucket in self._warm_buckets:
            return
        scratch = self._fresh_state()
        batch = self._zero_batch(bucket)
        _, out = self._masked_fn(scratch, batch, jnp.zeros((bucket,), bool))
        jax.block_until_ready(host_pack(out, batch.tuple_hash))
        self._warm_buckets.add(bucket)

    def step_masked(self, packets: ft.PacketBatch,
                    keep: np.ndarray) -> PipelineStepOutput:
        """One padded request batch through the loop: rows with ``keep ==
        False`` are padding — excluded from the tracker merge, the rule-table
        feedback and the packet stats (they count as ``padded``, like a
        sharded lane's skew rows).  The batch may be any pre-warmed bucket
        size; it is NOT tied to ``cfg.batch_size``.  The whole call is the
        ``octopus.step`` span, tagged with this dispatch's number (the
        ``dispatches`` count before it) and its bucket.  Its one device->host
        read is the host pack: the returned output's host-read fields
        (``pkt_actions``, ``drained.mask``/``tuple_id``, ``flow_actions``,
        ``flow_cls``, the counters) are numpy, ``flow_scores`` and the rest
        of ``drained`` stay on the device."""
        bucket = np.shape(packets.ts)[0]
        k = np.asarray(keep)
        if k.shape != (bucket,):
            raise ValueError(f"keep must have shape ({bucket},), got {k.shape}")
        n = int(k.sum())
        st = self.stats
        with span("octopus.step", st, dispatch=st.dispatches, bucket=bucket):
            with span("octopus.enqueue", st) as enq:
                self.state, out = self._masked_fn(self.state, packets,
                                                  jnp.asarray(k))
                pack = host_pack(out, packets.tuple_hash)
            self._warm_buckets.add(bucket)  # compiled now, whatever the path
            return self._complete(out, pack,
                                  lambda f, _: [_feedback_args(f, k)],
                                  host_s=enq.s, packets=n, padded=bucket - n)

    def _dispatch_chunk(self, batches: Sequence[ft.PacketBatch]
                        ) -> InflightDispatch:
        """Enqueue one fused ``scan_len`` chunk without blocking: the host
        stacking happens now (charged to ``host_us``), the ``lax.scan``
        dispatch returns future arrays, and the handle's ``wait`` blocks +
        applies the per-step feedback in order."""
        L = self.cfg.scan_len
        batches = list(batches)
        if len(batches) != L:
            raise ValueError(f"step_many needs exactly scan_len={L} "
                             f"microbatches, got {len(batches)}")
        for b in batches:
            self._check_batch(b)
        with span("octopus.enqueue", self.stats) as enq:
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                             *batches)
            self.state, out = self._chunk_fn(self.state, stacked)
            pack = host_pack(out, stacked.tuple_hash)
        n = L * self.cfg.batch_size

        def finish(host_extra_s: float) -> PipelineStepOutput:
            # one tuple per step (leading step axis), in step order, so
            # later verdicts overwrite earlier
            return self._complete(out, pack,
                                  lambda f, _: list(zip(*_feedback_args(f))),
                                  host_s=enq.s + host_extra_s, packets=n,
                                  steps=L)

        return InflightDispatch(finish, steps=L, packets=n)

    def step_many(self, batches: Sequence[ft.PacketBatch]):
        """Run exactly ``scan_len`` microbatches as ONE device dispatch
        (``lax.scan`` over the fused step) and fold all decisions into the
        rule table afterwards, in step order.  Returns the stacked outputs
        (leading ``scan_len`` axis) — or, with ``cfg.overlap``, an
        :class:`InflightDispatch` to be waited in dispatch order.  Feedback
        granularity is the chunk: rule-table updates land after the whole
        chunk computes."""
        h = self._dispatch_chunk(batches)
        return h if self.cfg.overlap else h.wait()

    def run(self, traffic: Iterable[ft.PacketBatch],
            steps: Optional[int] = None) -> PipelineStats:
        """Drive the loop from an iterable of microbatches (e.g. a
        :class:`repro.data.traffic.TrafficGenerator`, which streams forever —
        pass ``steps`` to bound it) and return the sustained stats.  With
        ``scan_len > 1`` microbatches dispatch in chunks of ``scan_len``; a
        final partial chunk (iterator exhausted or ``steps`` not a multiple)
        runs per-step.

        With ``cfg.overlap`` the loop is a double-buffered producer/consumer:
        chunk k+1 is pulled from the iterator and *enqueued* while chunk k
        executes on device, and chunk k's handle is waited (feedback + stats)
        only then — strictly in dispatch order, so the run is bit-identical
        to the eager loop.  The iterator pull is charged to ``host_us`` in
        BOTH modes, so overlap-on/off stats compare at the same boundary;
        wrap the source in :func:`repro.data.traffic.prefetch` to move batch
        *generation* onto a background thread as well."""
        it = iter(traffic)
        L = self.cfg.scan_len
        done = 0
        pending: Optional[InflightDispatch] = None

        def advance(handle: InflightDispatch, pull_s: float) -> None:
            nonlocal pending
            handle.add_host_time(pull_s)
            if not self.cfg.overlap:
                handle.wait()
                return
            if pending is not None:
                pending.wait()  # chunk k-1: lagged feedback, in step order
            pending = handle

        while steps is None or done < steps:
            want = L if steps is None else min(L, steps - done)
            # islice, not enumerate+break: never pull a batch beyond `steps`
            # (a generator reused across run() calls must not drop batches)
            with span("octopus.pull", self.stats) as pull:
                chunk = list(itertools.islice(it, want))
            pull_s = pull.s
            if not chunk:
                break
            if L > 1 and len(chunk) == L:
                advance(self._dispatch_chunk(chunk), pull_s)
            else:
                if L > 1:  # partial-chunk fallback: warm outside the timing
                    self._warm_step()
                for batch in chunk:
                    advance(self._dispatch_step(batch), pull_s)
                    pull_s = 0.0  # charge the pull to the first step only
            done += len(chunk)
        if pending is not None:
            pending.wait()  # drain the in-flight tail
        return self.stats

    def reset(self) -> None:
        """Fresh table, rule set and counters (compiled dispatches are kept)."""
        self.state = self._fresh_state()
        self.rules = decisions.RuleTable()
        self.stats = PipelineStats()

    # ------------------------------------------------------------- placement
    def plan(self) -> RoutePlan:
        """One RoutePlan over the matmuls the decision heads actually
        consume, in step order (packet engine under the ``pkt/`` name scope,
        then the flow engine under ``flow/``) — the single placement truth
        for the fused step.  Feature-only heads contribute no matmuls: the
        plan reflects the inference the step really dispatches.  The shapes
        are per scan iteration: chunked dispatch scans the same step body,
        so the placement is identical for every ``scan_len``."""
        use_pkt = self.cfg.pkt_head.needs_logits
        use_flow = self.cfg.flow_head.needs_logits

        def engines(px: jax.Array, fx_: jax.Array):
            out = []
            if use_pkt:
                with name_scope("pkt"):
                    out.append(self.packet_engine.fn(self.packet_engine.params, px))
            if use_flow:
                with name_scope("flow"):
                    out.append(self.flow_engine.fn(self.flow_engine.params, fx_))
            return tuple(out)

        return RoutePlan.trace(
            engines, self.packet_engine.abstract_input(self.cfg.batch_size),
            self.flow_engine.abstract_input(self.cfg.max_ready),
            config=self.runtime)

    def explain(self) -> str:
        """Placement report for the fused step: the combined plan plus the
        per-engine split (feature-only heads report their engine as
        skipped)."""
        plan = self.plan()
        pkt = plan.scoped("pkt", strip=True)
        flow = plan.scoped("flow", strip=True)
        c = self.cfg
        head = (f"OctopusPipeline: batch={c.batch_size} max_ready={c.max_ready} "
                f"flow_model={c.flow_model} table={c.table_size} top_n={c.top_n} "
                f"tracker={c.tracker} scan_len={c.scan_len}")
        if c.cold_size:
            head += f" cold={c.cold_size}({c.cold_policy})"
        head += f" heads={c.pkt_head.name}/{c.flow_head.name}"
        fmt = lambda p: ", ".join(f"{s.name}->{s.engine}" for s in p.steps)
        eng = lambda p, on: (f"({len(p)} matmuls): {fmt(p)}" if on
                             else "skipped (feature-only head)")
        return "\n".join([
            head, plan.explain(),
            f"  packet-engine {eng(pkt, c.pkt_head.needs_logits)}",
            f"  flow-engine {eng(flow, c.flow_head.needs_logits)}",
        ])
