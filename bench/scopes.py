"""The program's own names in a profiler trace: the ``jax.named_scope`` of
each device operation, and the program's host spans (``octopus.*``,
``repro.runtime.span``).

``bench/trace.py`` keeps device operations by their HLO names and only the
benchmark's own host spans (``bench.*``).  :func:`flatten` keeps the same
events plus the program's spans.  A TPU trace's operation events carry no
``op_name`` (their stats are offsets and durations), so :func:`label` gives
each device operation its ``scope`` from the text of the compiled step
program it ran in, where every instruction carries its ``op_name`` metadata
(:func:`op_names`); the ``bucket`` argument of the ``octopus.step`` span
around a step program says which bucket's program that was.  XLA's own
layout copies and tuples carry no scope of their own: ``scope`` gives them
that of the work that uses them, and ``own_scope`` keeps their own.
The reductions read that list, so they can be checked on a small recorded
trace:

- :func:`scope_seconds`: device seconds per top-level scope (the outermost of
  :data:`SCOPES` in an operation's path), the union of the scope's operation
  intervals, so operations nested inside a ``while`` count once;
- :func:`inherited_ops`: the operations whose scope is their user's;
- :func:`idle_gaps`: the device's idle time split by the innermost host span
  over each part of each gap, the program's spans among the candidates;
- :func:`top_ops`: ``trace.top_ops`` with each operation's scope.

``tools/scope_trace.py`` makes a traced run of a cell with these reductions.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import trace

# bound here: a traced run puts this module's flatten in trace.flatten's place
_bench_flatten = trace.flatten
PROGRAM_SPAN = "octopus."
SPAN_PREFIXES = (trace.SPAN_PREFIX, PROGRAM_SPAN)
# the step program's named scopes (serving/pipeline.py), outermost first
SCOPES = ("track.promote", "track.merge", "track.spill", "track.scrub",
          "drain", "engine.pkt", "engine.flow")
UNSCOPED = "(unscoped)"
DEPTH = 4  # steps an unscoped instruction searches its users for a scope


def flatten(profile) -> list[dict]:
    """``trace.flatten``'s events, then the program's ``octopus.*`` host
    spans (with their arguments under ``args``)."""
    events = _bench_flatten(profile)
    for plane in profile.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PROGRAM_SPAN):
                    continue
                e = {"plane": plane.name, "line": line.name, "name": ev.name,
                     "start_ns": int(ev.start_ns), "dur_ns": int(ev.duration_ns)}
                args = {k: v for k, v in ev.stats if not k.startswith("_")}
                if args:
                    e["args"] = args
                events.append(e)
    return events


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", re.M)
_REF = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(hlo_text: str) -> dict:
    """{instruction: (own op_name, scoped op_name)} of a compiled module's
    text.  The scoped one is the own one where that names one of
    :data:`SCOPES`, else that of the instruction's nearest user that does
    (breadth first, :data:`DEPTH` steps at most): XLA's own layout copies
    and tuples carry no scope, and belong to the work that needs them."""
    own, users = {}, defaultdict(list)
    for name, rest in _INSTR.findall(hlo_text):
        m = _OP_NAME.search(rest)
        own[name] = m.group(1) if m else ""
        for ref in _REF.findall(rest.split("metadata=", 1)[0]):
            users[ref].append(name)
    out = {}
    for name, path in own.items():
        seen, front = {name}, [name]
        for _ in range(DEPTH):
            if top_scope(path) != UNSCOPED or not front:
                break
            front = [u for f in front for u in users[f] if u not in seen]
            seen.update(front)
            path = next((own[u] for u in front
                         if top_scope(own[u]) != UNSCOPED), path)
        out[name] = (own[name], path)
    return out


def instruction(name: str) -> str:
    """``%while.120 = ... while(...)`` (or ``%while.120 while``) -> ``while.120``."""
    return trace.op_label(name).split(" ", 1)[0].lstrip("%")


def label(events: list[dict], names: dict) -> list[dict]:
    """Give each device operation its ``scope`` and ``own_scope``: the
    scoped and the own ``op_name`` of its instruction in the program it ran
    in (:func:`op_names`), '' where it has none.  ``names``
    maps each bucket to its program's :func:`op_names`; a step program
    takes the bucket of the ``octopus.step`` span it ran inside, else the
    largest."""
    steps = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"],
                    e.get("args", {}).get("bucket"))
                   for e in events if e["name"] == "octopus.step")
    step_starts = [s for s, _, _ in steps]
    default = max(names) if names else None
    progs = defaultdict(list)  # plane -> [(start, end, bucket)]
    for e in sorted(events, key=lambda e: e["start_ns"]):
        if e["line"] == trace.MODULES_LINE:
            k = bisect.bisect_right(step_starts, e["start_ns"]) - 1
            inside = k >= 0 and steps[k][1] >= e["start_ns"] and steps[k][2] in names
            progs[e["plane"]].append((e["start_ns"], e["start_ns"] + e["dur_ns"],
                                      steps[k][2] if inside else default))
    starts = {p: [s for s, _, _ in v] for p, v in progs.items()}
    for e in events:
        if e["line"] != trace.OPS_LINE:
            continue
        k = bisect.bisect_right(starts.get(e["plane"], []), e["start_ns"]) - 1
        b = progs[e["plane"]][k][2] if k >= 0 else default
        e["own_scope"], e["scope"] = names.get(b, {}).get(
            instruction(e["name"]), ("", ""))
    return events


def top_scope(path: str) -> str:
    """The outermost of :data:`SCOPES` in an ``op_name`` path."""
    for part in path.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def scope_seconds(events: list[dict], t0: int, t1: int,
                  key: str = "scope") -> dict:
    """{scope: device seconds in the window}: per top-level scope the union
    of its operations' intervals, clipped to [t0, t1), averaged over the
    device planes; largest first.  ``key="own_scope"`` counts each operation
    under its own ``op_name`` alone."""
    planes = trace.device_planes(events)
    iv: dict[tuple, list] = defaultdict(list)
    for e in events:
        if e["line"] != trace.OPS_LINE:
            continue
        s, f = max(e["start_ns"], t0), min(e["start_ns"] + e["dur_ns"], t1)
        if f > s:
            iv[(e["plane"], top_scope(e.get(key, "")))].append((s, f))
    tot: dict[str, int] = defaultdict(int)
    for (_, scope), spans in iv.items():
        tot[scope] += sum(f - s for s, f in trace._union(spans))
    k = max(len(planes), 1)
    return {s: ns / k / 1e9 for s, ns in sorted(tot.items(), key=lambda x: -x[1])}


def top_ops(events: list[dict], t0: int, t1: int, n: int = 10) -> list:
    """[[op name, scope, device seconds]] of the ``n`` operations that took
    most time in the window (``trace.top_ops``, with the scope)."""
    scope = {}
    for e in events:
        if e["line"] == trace.OPS_LINE:
            scope.setdefault(trace.op_label(e["name"]), top_scope(e.get("scope", "")))
    return [[name, scope.get(name, UNSCOPED), s]
            for name, s in trace.top_ops(events, t0, t1, n)]


def inherited_ops(events: list[dict], t0: int, t1: int, n: int = 10) -> list:
    """[[op name, scope, device seconds]] of the ``n`` operations that took
    most time in the window among those whose scope is their user's (their
    own ``op_name`` names none of :data:`SCOPES`), summed over occurrences,
    averaged over planes."""
    tot: dict[str, int] = defaultdict(int)
    scope = {}
    for e in events:
        if (e["line"] == trace.OPS_LINE and t0 <= e["start_ns"] < t1
                and top_scope(e.get("own_scope", "")) == UNSCOPED
                and top_scope(e.get("scope", "")) != UNSCOPED):
            name = trace.op_label(e["name"])
            tot[name] += e["dur_ns"]
            scope[name] = top_scope(e["scope"])
    k = max(len(trace.device_planes(events)), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, scope[name], ns / k / 1e9] for name, ns in best]


def idle_gaps(events: list[dict], t0: int, t1: int, n: int = 10) -> list:
    """[[host activity, seconds]]: the device's idle time in the window on
    the first device plane.  A gap inside a device program (between its own
    operations) is named so; any other gap is split at the host spans'
    edges, and each part goes to the latest-started ``bench.*`` or
    ``octopus.*`` span covering it (the innermost, as spans nest), else to
    ``no bench span``.  ``x<k>``: the gaps a name holds part of.  Unlike
    ``trace.idle_gaps``, which names a whole gap by the span at its middle,
    a gap that spans several steps of the host loop is shared among them."""
    planes = trace.device_planes(events)
    if not planes:
        return []
    busy = trace.busy_intervals(events, planes[0], t0, t1)
    gaps, cur = [], t0
    for s, f in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, f)
    if t1 > cur:
        gaps.append((cur, t1))
    # by start, the longer of two spans that start together first: the
    # latest in this order that covers an instant is the innermost there
    spans = sorted(((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                    for e in events if e["name"].startswith(SPAN_PREFIXES)
                    and e["name"] != trace.WINDOW_SPAN
                    and not e["plane"].startswith(trace.DEVICE_PREFIX)),
                   key=lambda sp: (sp[0], -sp[1]))
    starts = [a for a, _, _ in spans]
    progs = trace._union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                          for e in events if e["plane"] == planes[0]
                          and e["line"] == trace.MODULES_LINE])
    pstarts = [a for a, _ in progs]
    tot: dict[str, int] = defaultdict(int)
    cnt: dict[str, int] = defaultdict(int)
    for s, f in gaps:
        k = bisect.bisect_right(pstarts, (s + f) // 2) - 1
        if k >= 0 and progs[k][1] >= (s + f) // 2:
            parts = {"inside a device program": f - s}
        else:
            over = [sp for sp in spans[:bisect.bisect_left(starts, f)] if sp[1] > s]
            edges = sorted({s, f} | {x for a, b, _ in over for x in (a, b) if s < x < f})
            parts = defaultdict(int)
            for a, b in zip(edges, edges[1:]):
                name = next((sp[2] for sp in reversed(over)
                             if sp[0] <= a and sp[1] >= b), "no bench span")
                parts[name] += b - a
        for name, ns in parts.items():
            tot[name] += ns
            cnt[name] += 1
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[f"{name} x{cnt[name]}", ns / 1e9] for name, ns in best]


def dispatches(events: list[dict], step_program: str, t0: int, t1: int) -> int:
    """Step programs started in the window, on the first device plane."""
    planes = trace.device_planes(events)
    return sum(1 for e in events if planes and e["plane"] == planes[0]
               and e["line"] == trace.MODULES_LINE and step_program in e["name"]
               and t0 <= e["start_ns"] < t1)


def reduce(events: list[dict], step_program: str) -> dict:
    """What this module reads from one trace."""
    t0, t1 = trace.window(events)
    return {"dispatches": dispatches(events, step_program, t0, t1),
            "step_device_s": trace.program_seconds(events, step_program, t0, t1),
            "device_scopes": scope_seconds(events, t0, t1),
            "device_scopes_own": scope_seconds(events, t0, t1, "own_scope"),
            "inherited_ops": inherited_ops(events, t0, t1),
            "device_ops": top_ops(events, t0, t1),
            "idle_gaps": idle_gaps(events, t0, t1)}
