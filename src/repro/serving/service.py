"""Async batch serving frontend over the streaming pipelines.

The paper's Octopus sits on the data plane and absorbs whatever arrival
pattern the wire delivers; :class:`~repro.serving.pipeline.OctopusPipeline`
is the compute analogue, but its ``run()`` loop is synchronous and fed by a
single generator.  Serving many concurrent clients with uneven, bursty
arrivals is a queueing problem in front of a fixed-shape inference engine —
the shape dataplane co-processors (and batch LLM servers like SHARK's
``service_v1``) all share:

  * a **request queue** accepting per-client packet microbatches of
    arbitrary size (:meth:`OctopusService.submit`),
  * a **batcher** that coalesces queued requests and pads the coalesced
    batch to the nearest pre-warmed ``bucket`` size — every bucket's masked
    entry point is compiled at startup, so ragged arrivals *never retrace*
    (``trace_count`` stays flat after :meth:`start`; asserted in tests),
  * **inflight buffer pooling**: the host staging arrays a dispatch packs
    requests into are reused per bucket, not reallocated per request,
  * **admission control**: when queued packets exceed ``depth_budget``, new
    submissions either get an explicit :class:`Rejected` result (``"shed"``)
    or wait for space (``"block"``), policy-selectable,
  * **latency observability**: per-client and global p50/p99 queue-wait and
    end-to-end latency (bounded :class:`~repro.serving.pipeline.LatencyReservoir`
    samples) plus queue-depth high-water marks in :class:`ServiceStats`.

The device dispatch stays *serialized* — the tracker state is a sequential
carry, there is exactly one engine — but with ``ServiceConfig.offload``
(the default) it runs on a single-thread executor instead of the event
loop: clients keep enqueueing while a device step executes, instead of only
in the ``batch_wait_s`` grace window, so the next dispatch coalesces what
arrived *during* the current one.  All bookkeeping (futures, queue depth,
admission events) stays on the loop side — only the pack + ``step_masked``
block moves off it.  A failing dispatch resolves every coalesced request's
future with the error, returns the staging buffer to the pool, and restores
the queue depth, so admission control never wedges and the service keeps
serving (regression-tested).  ``asyncio`` here buys exactly what the
paper's wire interface buys the FPGA: many independent arrival processes
multiplexed into one fixed-shape compute loop.  Clients run closed-loop
(``await submit(...)``) and the batcher's coalescing is where concurrency
turns into throughput: N clients awaiting together become one padded bucket
dispatch instead of N tiny ones.

Correctness: a request of size ``b < bucket`` padded-then-served produces
verdicts and tracker state **bit-identical** to serving it through the
unpadded synchronous pipeline (the keep-mask machinery from the sharded
lanes; differentially tested in ``tests/test_service.py``).
"""
from __future__ import annotations

import asyncio
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Optional, Union

import jax.numpy as jnp
import numpy as np

from repro.core.flow_tracker import PacketBatch
from repro.data.traffic import TrafficGenerator
from repro.runtime import span
from repro.serving.pipeline import LatencyReservoir, OctopusPipeline

ADMISSION_POLICIES = ("shed", "block")

# PacketBatch scalar (per-packet) int32 leaves, in field order; payload is
# the one 2-D leaf and is staged separately.
_SCALAR_FIELDS = ("ts", "size", "dir", "flags", "proto", "tuple_hash")


@dataclass(frozen=True)
class ServiceConfig:
    """Knobs of the serving frontend (see docs/ARCHITECTURE.md for the
    knob table)."""

    buckets: tuple[int, ...] = (32, 64, 128, 256)  # pre-warmed batch shapes
    depth_budget: int = 1024  # max queued packets before admission control
    admission: str = "shed"  # "shed" -> Rejected result | "block" -> await
    batch_wait_s: float = 0.0  # grace the batcher waits to coalesce more
    sample_capacity: int = 1024  # latency reservoir depth (per scope)
    pool_depth: int = 4  # staging buffers retained per bucket
    offload: bool = True  # run pack + device dispatch on an executor thread
    # (event loop stays free to accept submits); False = inline (the old
    # behavior, kept for the overlap-on/off bench twins)

    def __post_init__(self):
        if not self.buckets or any(b <= 0 for b in self.buckets):
            raise ValueError(f"buckets must be positive, got {self.buckets}")
        if tuple(sorted(set(self.buckets))) != tuple(self.buckets):
            raise ValueError(f"buckets must be strictly increasing, "
                             f"got {self.buckets}")
        if self.admission not in ADMISSION_POLICIES:
            raise ValueError(f"admission must be one of {ADMISSION_POLICIES}, "
                             f"got {self.admission!r}")
        if self.depth_budget <= 0 or self.pool_depth <= 0:
            raise ValueError("depth_budget and pool_depth must be positive")
        if self.batch_wait_s < 0:
            raise ValueError(f"batch_wait_s must be >= 0, "
                             f"got {self.batch_wait_s}")


@dataclass(frozen=True)
class ServeResult:
    """One served request: per-packet verdicts in the request's own order."""

    client_id: int
    # (n,) int32 packet-head verdicts (default binary head: 0 allow / 1 deny;
    # pluggable heads — PipelineConfig.pkt_head — define their own codes)
    pkt_actions: np.ndarray
    bucket: int  # largest bucket this request ACTUALLY dispatched in — the
    # coalesced dispatch's bucket, not the request's own size class (0 for
    # the empty-submit fast path, which never dispatches)
    queue_wait_s: float  # enqueue -> dispatch start
    e2e_s: float  # enqueue -> verdicts ready
    buckets: tuple[int, ...] = ()  # per-chunk dispatch buckets, in order
    # (an oversize submit splits into several chunks; each records its own)
    dispatches: tuple[int, ...] = ()  # per-chunk pipeline dispatch numbers
    # (the ``dispatch`` argument of that step's ``octopus.step`` span)


@dataclass(frozen=True)
class Rejected:
    """Admission-control shed: the queue was over budget when this request
    arrived.  An explicit result, not an exception — shedding is a normal
    dataplane outcome the client is expected to handle (retry, back off)."""

    client_id: int
    packets: int  # size of the rejected request
    queue_depth: int  # queued packets at rejection time
    depth_budget: int


SubmitOutcome = Union[ServeResult, Rejected]


@dataclass
class ClientStats:
    """Per-client slice of the service counters."""

    requests: int = 0
    submitted: int = 0  # packets offered (incl. shed)
    served: int = 0  # packets that got verdicts
    shed: int = 0  # packets rejected by admission control
    wait: LatencyReservoir = field(default_factory=LatencyReservoir)
    e2e: LatencyReservoir = field(default_factory=LatencyReservoir)


@dataclass
class ServiceStats:
    """Global service counters + per-client breakdown.  The latency
    reservoirs sample in **microseconds**; idle percentiles are ``nan``
    (the ``PipelineStats`` convention)."""

    requests: int = 0
    served_requests: int = 0
    shed_requests: int = 0
    submitted: int = 0  # packets offered
    served: int = 0  # packets dispatched + answered
    shed: int = 0  # packets rejected
    dispatches: int = 0  # bucket dispatches issued
    coalesced: int = 0  # requests merged into those dispatches
    padded: int = 0  # bucket pad rows dispatched (masked)
    depth_hwm: int = 0  # queue-depth high-water mark (packets)
    pool_hits: int = 0
    pool_misses: int = 0
    failed_dispatches: int = 0  # dispatches whose step raised
    failed: int = 0  # packets answered with an error instead of verdicts
    host_s: float = 0.0  # dispatch host share: staging-buffer pack, copies
    # to and from the device (the ``octopus.pack``, ``.h2d`` and ``.d2h`` spans)
    device_s: float = 0.0  # time blocked on the device: the pipeline's
    # ``octopus.wait`` over this service's dispatches
    spans: dict = field(default_factory=dict)  # name -> SpanTotal
    started_at: float = 0.0  # perf_counter anchor set by start(); 0 = never
    stopped_at: float = 0.0  # freeze anchor set by stop(); 0 while running
    wait: LatencyReservoir = field(default_factory=LatencyReservoir)
    e2e: LatencyReservoir = field(default_factory=LatencyReservoir)
    clients: dict[int, ClientStats] = field(default_factory=dict)

    def client(self, client_id: int) -> ClientStats:
        st = self.clients.get(client_id)
        if st is None:
            cap = self.wait.capacity
            st = self.clients[client_id] = ClientStats(
                wait=LatencyReservoir(cap), e2e=LatencyReservoir(cap))
        return st

    @property
    def wall_s(self) -> float:
        """Service wall clock, snapshotted at READ time while the service
        runs and frozen at :meth:`OctopusService.stop`.  (It used to be a
        field refreshed only inside the dispatcher, so any read after the
        last dispatch — an idle tail, a post-run report — used a stale
        clock and overstated ``pkt_per_s``.)"""
        if not self.started_at:
            return 0.0
        end = self.stopped_at if self.stopped_at else time.perf_counter()
        return max(end - self.started_at, 0.0)

    @property
    def pkt_per_s(self) -> float:
        """Sustained served packet rate over the service's wall clock."""
        wall = self.wall_s
        return self.served / wall if wall > 0 else 0.0

    @property
    def host_us(self) -> float:
        """Mean host share per dispatch (staging pack + device copies)."""
        return self.host_s / self.dispatches * 1e6 if self.dispatches else float("nan")

    @property
    def device_us(self) -> float:
        """Mean time per dispatch blocked on the device (the pipeline's
        ``octopus.wait``; its enqueue, read-back and feedback are the
        pipeline's ``host_us``)."""
        return self.device_s / self.dispatches * 1e6 if self.dispatches else float("nan")


class _BufferPool:
    """Per-bucket pool of host staging arrays (one PacketBatch worth of
    numpy leaves + a keep mask).  ``jnp.asarray`` copies host memory into
    the device buffer at dispatch and the dispatcher blocks on the result,
    so a released buffer is safe to refill immediately — requests reuse the
    staging arrays instead of allocating fresh ones per dispatch."""

    def __init__(self, pay_bytes: int, depth: int, stats: ServiceStats):
        self.pay_bytes = pay_bytes
        self.depth = depth
        self.stats = stats
        self._free: dict[int, list[dict]] = {}

    def acquire(self, bucket: int) -> dict:
        free = self._free.setdefault(bucket, [])
        if free:
            self.stats.pool_hits += 1
            return free.pop()
        self.stats.pool_misses += 1
        buf = {f: np.zeros(bucket, np.int32) for f in _SCALAR_FIELDS}
        buf["payload"] = np.zeros((bucket, self.pay_bytes), np.int32)
        buf["keep"] = np.zeros(bucket, bool)
        return buf

    def release(self, buf: dict) -> None:
        free = self._free.setdefault(len(buf["keep"]), [])
        if len(free) < self.depth:
            free.append(buf)


@dataclass
class _Pending:
    """One queued request chunk (a submit larger than the largest bucket
    splits into several, each at most one bucket)."""

    client_id: int
    leaves: dict  # host numpy views of the PacketBatch leaves
    n: int
    enqueued_at: float
    future: asyncio.Future
    dispatched_at: float = 0.0
    bucket: int = 0  # the bucket this chunk actually dispatched in
    dispatch: int = -1  # the pipeline dispatch number it rode in


class OctopusService:
    """Asyncio serving frontend over an :class:`OctopusPipeline` (or
    :class:`~repro.serving.sharded.ShardedOctopusPipeline` — both expose the
    same ``warm_bucket``/``step_masked`` masked entry surface).

    Lifecycle::

        service = OctopusService(pipeline, ServiceConfig(buckets=(32, 64)))
        await service.start()        # pre-warms every bucket entry point
        result = await service.submit(batch, client_id=7)
        await service.stop()         # drains the queue, then stops

    or ``async with OctopusService(...) as service: ...``.
    """

    def __init__(self, pipeline: OctopusPipeline,
                 cfg: ServiceConfig = ServiceConfig()):
        self.pipeline = pipeline
        self.cfg = cfg
        self.stats = ServiceStats(
            wait=LatencyReservoir(cfg.sample_capacity),
            e2e=LatencyReservoir(cfg.sample_capacity))
        self._pool = _BufferPool(pipeline.cfg.pay_bytes, cfg.pool_depth,
                                 self.stats)
        self._queue: deque[_Pending] = deque()
        self._depth = 0  # queued packets
        self._work: Optional[asyncio.Event] = None
        self._space: Optional[asyncio.Event] = None
        self._dispatcher: Optional[asyncio.Task] = None
        self._executor: Optional[ThreadPoolExecutor] = None
        self._stopping = False

    # ------------------------------------------------------------- lifecycle
    @property
    def trace_count(self) -> int:
        """The pipeline's retrace counter — flat after :meth:`start` is the
        no-retrace-on-ragged-arrivals proof."""
        return self.pipeline.trace_count

    @property
    def queue_depth(self) -> int:
        """Currently queued packets (admission control's input)."""
        return self._depth

    async def start(self) -> None:
        """Pre-compile every bucket's masked entry point (outside any timed
        region) and start the dispatcher task (plus its single-thread
        dispatch executor when ``cfg.offload``)."""
        if self._dispatcher is not None:
            raise RuntimeError("service already started")
        for b in self.cfg.buckets:
            self.pipeline.warm_bucket(b)
        self._work = asyncio.Event()
        self._space = asyncio.Event()
        self._stopping = False
        if self.cfg.offload:
            # exactly one worker: the tracker state is a sequential carry,
            # so dispatches must serialize — the thread only exists to keep
            # the event loop free while a device step blocks
            self._executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="octopus-dispatch")
        self.stats.started_at = time.perf_counter()
        self.stats.stopped_at = 0.0
        self._dispatcher = asyncio.create_task(self._dispatch_loop())

    async def stop(self) -> None:
        """Drain the queue (every accepted request still gets its result),
        then stop the dispatcher and freeze the wall clock."""
        if self._dispatcher is None:
            return
        self._stopping = True
        self._work.set()
        await self._dispatcher
        self._dispatcher = None
        if self._executor is not None:
            self._executor.shutdown(wait=True)
            self._executor = None
        self.stats.stopped_at = time.perf_counter()

    async def __aenter__(self) -> "OctopusService":
        await self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    # ---------------------------------------------------------------- submit
    def _host_leaves(self, packets: PacketBatch) -> dict:
        leaves = {f: np.asarray(getattr(packets, f)) for f in _SCALAR_FIELDS}
        leaves["payload"] = np.asarray(packets.payload)
        if leaves["payload"].shape[1:] != (self.pipeline.cfg.pay_bytes,):
            raise ValueError(
                f"payload width {leaves['payload'].shape[1:]} does not match "
                f"the pipeline's pay_bytes={self.pipeline.cfg.pay_bytes}")
        return leaves

    async def submit(self, packets: PacketBatch,
                     client_id: int = 0) -> SubmitOutcome:
        """Queue one microbatch (any size) and await its verdicts.

        Admission control runs *before* anything is enqueued, against the
        whole request: ``"shed"`` returns :class:`Rejected` immediately when
        the queue is over budget, ``"block"`` waits for space.  A request
        larger than the largest bucket is split into bucket-sized chunks
        that dispatch in order (still one result)."""
        if self._dispatcher is None:
            raise RuntimeError("service not started (use `async with` or "
                               "`await service.start()`)")
        leaves = self._host_leaves(packets)
        n = int(leaves["ts"].shape[0])
        if n == 0:  # empty submits answer immediately and skew nothing
            return ServeResult(client_id, np.zeros(0, np.int32), 0, 0.0, 0.0)
        gstats = self.stats
        cstats = gstats.client(client_id)
        gstats.requests += 1
        cstats.requests += 1
        gstats.submitted += n
        cstats.submitted += n

        if self._depth + n > self.cfg.depth_budget:
            if self.cfg.admission == "shed":
                gstats.shed_requests += 1
                cstats.shed += n
                gstats.shed += n
                return Rejected(client_id, n, self._depth,
                                self.cfg.depth_budget)
            while self._depth + n > self.cfg.depth_budget:
                self._space.clear()
                await self._space.wait()

        # enqueue every chunk before the first await, so admission order is
        # submission order (a gather of submits sheds deterministically)
        top = self.cfg.buckets[-1]
        loop = asyncio.get_running_loop()
        now = time.perf_counter()
        chunks: list[_Pending] = []
        for off in range(0, n, top):
            m = min(top, n - off)
            sl = {k: v[off:off + m] for k, v in leaves.items()}
            chunks.append(_Pending(client_id, sl, m, now, loop.create_future()))
        self._queue.extend(chunks)
        self._depth += n
        gstats.depth_hwm = max(gstats.depth_hwm, self._depth)
        self._work.set()

        # return_exceptions so every chunk's error is consumed here — one
        # failed dispatch fails the whole request (partial verdicts would be
        # unusable), without "exception never retrieved" noise from siblings
        results = await asyncio.gather(*(c.future for c in chunks),
                                       return_exceptions=True)
        errors = [r for r in results if isinstance(r, BaseException)]
        if errors:
            raise errors[0]
        done = time.perf_counter()
        actions = np.concatenate(results)
        wait_s = chunks[0].dispatched_at - now
        e2e_s = done - now
        gstats.served_requests += 1
        gstats.served += n
        cstats.served += n
        for st in (gstats, cstats):
            st.wait.add(wait_s * 1e6)
            st.e2e.add(e2e_s * 1e6)
        buckets = tuple(c.bucket for c in chunks)
        return ServeResult(client_id, actions, max(buckets), wait_s, e2e_s,
                           buckets, tuple(c.dispatch for c in chunks))

    # ------------------------------------------------------------- dispatcher
    def _bucket_for(self, n: int) -> int:
        for b in self.cfg.buckets:
            if n <= b:
                return b
        raise AssertionError(f"chunk of {n} exceeds the largest bucket "
                             f"{self.cfg.buckets[-1]}")  # pragma: no cover

    def _take_coalesced(self) -> list[_Pending]:
        """Pop a FIFO run of requests that fits the largest bucket (always
        at least one — chunks never exceed it)."""
        top = self.cfg.buckets[-1]
        reqs = [self._queue.popleft()]
        total = reqs[0].n
        while self._queue and total + self._queue[0].n <= top:
            nxt = self._queue.popleft()
            reqs.append(nxt)
            total += nxt.n
        return reqs

    def _dispatch_blocking(self, reqs: list[_Pending]
                           ) -> tuple[np.ndarray, dict, int, float, float]:
        """The blocking half of one dispatch — pack a coalesced run into a
        pooled staging buffer, pad to the bucket, run the masked step.  Runs
        on the dispatch executor under ``cfg.offload`` (inline otherwise);
        it touches no asyncio state, only the pipeline and the pool.  On a
        failing step the buffer is returned to the pool HERE (this side owns
        it); futures and queue depth are the loop side's to restore.
        Returns ``(actions, buf, bucket, host_s, device_s)``."""
        total = sum(r.n for r in reqs)
        bucket = self._bucket_for(total)
        st, pipe = self.stats, self.pipeline
        buf = None
        try:
            with span("octopus.pack", st) as pack:
                buf = self._pool.acquire(bucket)
                off = 0
                for r in reqs:
                    for f in _SCALAR_FIELDS:
                        buf[f][off:off + r.n] = r.leaves[f]
                    buf["payload"][off:off + r.n] = r.leaves["payload"]
                    off += r.n
                for f in _SCALAR_FIELDS:  # zero the pad tail: stale rows out
                    buf[f][total:] = 0
                buf["payload"][total:] = 0
                buf["keep"][:total] = True
                buf["keep"][total:] = False

                t_dispatch = time.perf_counter()
                for r in reqs:
                    r.dispatched_at = t_dispatch
                    r.bucket = bucket
                    r.dispatch = pipe.stats.dispatches  # step_masked's number
            with span("octopus.h2d", st) as h2d:
                batch = PacketBatch(
                    **{f: jnp.asarray(buf[f]) for f in _SCALAR_FIELDS},
                    payload=jnp.asarray(buf["payload"]))
            waited = pipe.stats.device_s
            out = pipe.step_masked(batch, buf["keep"])
            with span("octopus.d2h", st) as d2h:
                actions = np.asarray(out.pkt_actions)
            return (actions, buf, bucket, pack.s + h2d.s + d2h.s,
                    pipe.stats.device_s - waited)
        except BaseException:
            if buf is not None:
                self._pool.release(buf)
            raise

    async def _dispatch_one(self, reqs: list[_Pending]) -> None:
        """One full dispatch: run the blocking half (off-loop under
        ``cfg.offload``), then answer every coalesced request with its slice
        of the verdicts — or, if the step raised, with the error.  Queue
        depth and the space event are restored on BOTH paths, so admission
        control never wedges on a failing dispatch."""
        total = sum(r.n for r in reqs)
        try:
            # the loop's wait on the blocking half, the hand-offs to and from
            # the executor thread included
            with span("octopus.dispatch", self.stats):
                if self._executor is not None:
                    actions, buf, bucket, host_s, device_s = \
                        await asyncio.get_running_loop().run_in_executor(
                            self._executor, self._dispatch_blocking, reqs)
                else:
                    actions, buf, bucket, host_s, device_s = \
                        self._dispatch_blocking(reqs)
        except Exception as e:
            self.stats.failed_dispatches += 1
            self.stats.failed += total
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(e)
        else:
            with span("octopus.answer", self.stats):
                off = 0
                for r in reqs:
                    r.future.set_result(actions[off:off + r.n].copy())
                    off += r.n
            self._pool.release(buf)
            self.stats.dispatches += 1
            self.stats.coalesced += len(reqs)
            self.stats.padded += bucket - total
            self.stats.host_s += host_s
            self.stats.device_s += device_s
        finally:
            self._depth -= total
            self._space.set()

    async def _next_batch(self) -> Optional[list[_Pending]]:
        """Wait for queued requests and take a coalesced run of them; None
        once the service is stopping with nothing left."""
        while True:
            await self._work.wait()
            if not self._queue:
                if self._stopping:
                    return None
                self._work.clear()
                continue
            if self.cfg.batch_wait_s > 0:
                # coalescing grace: let concurrent clients land their
                # submits before the bucket is chosen
                await asyncio.sleep(self.cfg.batch_wait_s)
            else:
                # yield once so a gather of submits enqueues as one wave
                await asyncio.sleep(0)
            if self._queue:
                return self._take_coalesced()

    async def _dispatch_loop(self) -> None:
        while True:
            # the batcher's time between dispatches: the clients it yields
            # to run their submits inside this span
            with span("octopus.batch", self.stats):
                reqs = await self._next_batch()
            if reqs is None:
                return
            await self._dispatch_one(reqs)


async def serve_stream(service: OctopusService, gen: TrafficGenerator, *,
                       requests: int,
                       client_id: Optional[int] = None) -> list[SubmitOutcome]:
    """Closed-loop client: submit ``requests`` microbatches from one seeded
    generator sequentially (each awaited before the next — the arrival
    process a real port presents) and return the outcomes.  Run several of
    these under ``asyncio.gather`` for a multi-client load."""
    cid = gen.client_id if client_id is None else client_id
    results: list[SubmitOutcome] = []
    for batch in gen.batches(requests):
        results.append(await service.submit(batch, client_id=cid))
    return results


__all__ = ["OctopusService", "ServiceConfig", "ServiceStats", "ClientStats",
           "ServeResult", "Rejected", "ADMISSION_POLICIES", "serve_stream"]
