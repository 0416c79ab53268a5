"""Placement routing (paper §2.3, §3.2.3): the utilization model and the
per-matmul :class:`Route` decision, parameterized by :class:`RuntimeConfig`
instead of module globals.

The utilization model mirrors the paper's analysis: a (M,K)x(K,N) matmul on a
``T×T`` systolic array achieves ``util = K/⌈K⌉_T · N/⌈N⌉_T`` MAC-occupancy
(fill of the stationary tile), with an additional M-side penalty for streams
shorter than the array's fill depth.  The paper's 32x32-array example — layer 1
(10,3)x(3,32): 9.3% — is reproduced by this model (see tests).

While a :func:`record_routes` block is active every decision is appended to
the recorder — that is how :class:`repro.runtime.plan.RoutePlan` observes a
model trace without the model knowing about plans.
"""
from __future__ import annotations

from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass
from typing import Iterator, List, Optional

import jax

from repro.common.util import ceil_div
from repro.runtime.config import RuntimeConfig, current_runtime


@dataclass(frozen=True)
class Route:
    path: str  # "arype" | "vpe"
    util: float
    reason: str


@dataclass(frozen=True)
class RouteRecord:
    """One recorded placement decision (name may be auto-assigned later).

    ``quantized`` marks decisions whose execution will take the int8 engine
    path (config has ``quantize`` on and a scale entry for this name)."""

    name: Optional[str]
    m: int
    k: int
    n: int
    route: Route
    quantized: bool = False


_recorder: ContextVar[Optional[List[RouteRecord]]] = ContextVar("route_recorder", default=None)
_name_scope: ContextVar[str] = ContextVar("route_name_scope", default="")


@contextmanager
def record_routes() -> Iterator[List[RouteRecord]]:
    """Collect every :func:`route_matmul` decision made inside the block."""
    records: List[RouteRecord] = []
    token = _recorder.set(records)
    try:
        yield records
    finally:
        _recorder.reset(token)


@contextmanager
def name_scope(label: str) -> Iterator[None]:
    """Prefix recorded matmul names with ``label/`` within the block (nesting
    joins with ``/``).  Lets a composite trace — e.g. the streaming pipeline's
    packet + flow engines — keep its sub-models distinguishable inside one
    :class:`repro.runtime.plan.RoutePlan`.  The block is also a
    ``jax.named_scope``, so operations traced inside it carry the same label
    in their ``op_name`` (and in a device trace)."""
    outer = _name_scope.get()
    token = _name_scope.set(f"{outer}{label}/")
    try:
        with jax.named_scope(label):
            yield
    finally:
        _name_scope.reset(token)


def current_scope() -> str:
    """The active :func:`name_scope` prefix ("" outside any scope)."""
    return _name_scope.get()


@contextmanager
def lane_scope(lane: int) -> Iterator[None]:
    """:func:`name_scope` for one serving lane (``lane<i>/``) — the sharded
    pipeline traces each lane's engines under its own scope, so
    ``RoutePlan.scoped(f"lane{i}")`` extracts any single lane's placement
    from the composite multi-lane plan."""
    with name_scope(f"lane{lane}"):
        yield


def systolic_utilization(m: int, k: int, n: int, array: int) -> float:
    """The paper's utilization definition (§3.2.3): useful MACs over
    array-slots x stream-cycles for an (m,k)x(k,n) matmul on an array x array
    systolic grid.  Reproduces the paper's 9.3% for (10,3)x(3,32) on 32x32."""
    kb, nb = ceil_div(k, array), ceil_div(n, array)
    useful = m * k * n
    slots = kb * nb * m * array * array
    return useful / slots


def mxu_utilization(m: int, k: int, n: int, tile: Optional[int] = None,
                    fill: Optional[int] = None) -> float:
    """TPU routing cost model: stationary-tile fill (K, N padding waste) plus
    the sublane granularity penalty on the streamed M dimension.

    ``tile``/``fill`` default from the *ambient* runtime (not the frozen
    class defaults, which would silently ignore an active
    ``runtime_overrides(mxu_tile=...)`` when called directly)."""
    if tile is None or fill is None:
        cfg = current_runtime()
        tile = cfg.mxu_tile if tile is None else tile
        fill = cfg.fill_depth if fill is None else fill
    fill_k = k / (ceil_div(k, tile) * tile)
    fill_n = n / (ceil_div(n, tile) * tile)
    stream = m / (ceil_div(m, fill) * fill)
    return fill_k * fill_n * stream


def route_matmul(m: int, k: int, n: int, *, config: Optional[RuntimeConfig] = None,
                 name: Optional[str] = None) -> Route:
    """Decide the engine for an (m,k)x(k,n) matmul under ``config`` (ambient
    runtime when None).  Records the decision if a plan trace is active."""
    cfg = config if config is not None else current_runtime()
    util = mxu_utilization(m, k, n, tile=cfg.mxu_tile, fill=cfg.fill_depth)
    if cfg.policy == "arype_only":
        route = Route("arype", util, "forced")
    elif cfg.policy == "vpe_only":
        route = Route("vpe", util, "forced")
    elif util < cfg.tau and m * k * n <= cfg.vpe_max_elems:
        route = Route("vpe", util, f"util {util:.3f} < {cfg.tau} and working set fits VPU path")
    else:
        route = Route("arype", util, f"util {util:.3f}")
    records = _recorder.get()
    if records is not None:
        scope = _name_scope.get()
        scoped = f"{scope}{name}" if name is not None else (scope or None)
        quantized = bool(
            cfg.quantize and cfg.quant_scales is not None
            and cfg.quant_scales.lookup(name, scope) is not None)
        records.append(RouteRecord(scoped, m, k, n, route, quantized))
    return route
