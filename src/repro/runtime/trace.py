"""Host spans on the device trace's clock.

:class:`span` times one interval of host work twice over: it opens a
``jax.profiler.TraceAnnotation`` (visible in a profiler trace, beside the
device's operations, only while the profiler runs) and folds the interval's
``perf_counter`` duration into a per-name :class:`SpanTotal` table on the
stats object it is given (any object with a ``spans`` dict, such as
``PipelineStats`` or ``ServiceStats``).  The tables stay in memory; a reader
snapshots ``stats.spans`` and subtracts two snapshots for a window.

There is no switch: the profiler running is the only "on".  With it off a
span costs one ``TraceAnnotation`` (about a microsecond) and a dict update.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import jax


class SpanTotal(NamedTuple):
    """Every interval recorded under one span name."""

    count: int
    total_s: float
    max_s: float

    def add(self, dt: float) -> "SpanTotal":
        return SpanTotal(self.count + 1, self.total_s + dt, max(self.max_s, dt))


EMPTY = SpanTotal(0, 0.0, 0.0)


class span:
    """``with span(name, stats, **meta) as s:`` — one host interval, written
    to the profiler's trace as ``name`` (``meta`` as its arguments) and added
    to ``stats.spans[name]``; ``s.s`` holds its duration after the block."""

    __slots__ = ("name", "into", "s", "_ann", "_t0")

    def __init__(self, name: str, into, **meta):
        self.name = name
        self.into = into
        self.s = 0.0
        self._ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        spans = self.into.spans
        spans[self.name] = spans.get(self.name, EMPTY).add(self.s)


__all__ = ["SpanTotal", "span"]
