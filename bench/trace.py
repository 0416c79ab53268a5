"""Profiler traces: capture one window, flatten it to plain events, and
reduce the events to the numbers the per-layer metrics read.

A trace is kept as a list of events ``{"plane", "line", "name", "start_ns",
"dur_ns"}``: device operations and programs from the device planes
(``/device:TPU:<n>``, lines ``XLA Ops`` and ``XLA Modules``) and the
benchmark's own host spans (names starting with ``bench.``) from the host
plane.  The reductions below take that list, so they can be checked on a
small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"  # opened by the harness around the traced window


def flatten(profile) -> list[dict]:
    """Events of a ``jax.profiler.ProfileData`` that the reductions read."""
    events = []
    for plane in profile.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name, "start_ns": int(ev.start_ns),
                               "dur_ns": int(ev.duration_ns)})
    return events


def load_dir(logdir: str) -> list[dict]:
    """Flattened events of the one ``.xplane.pb`` the profiler wrote under
    ``logdir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found {files}")
    return flatten(ProfileData.from_file(files[0]))


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_planes(events: list[dict]) -> list[str]:
    return sorted({e["plane"] for e in events if e["plane"].startswith(DEVICE_PREFIX)})


def busy_intervals(events: list[dict], plane: str, t0: int, t1: int
                   ) -> list[tuple[int, int]]:
    """Union of the device operations' intervals on ``plane``, clipped to
    the window [t0, t1)."""
    iv = []
    for e in events:
        if e["plane"] == plane and e["line"] == OPS_LINE:
            s, f = max(e["start_ns"], t0), min(e["start_ns"] + e["dur_ns"], t1)
            if f > s:
                iv.append((s, f))
    return _union(iv)


def busy_seconds(events: list[dict], t0: int, t1: int) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    device planes in the trace."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    tot = sum(sum(f - s for s, f in busy_intervals(events, p, t0, t1))
              for p in planes)
    return tot / len(planes) / 1e9


def program_seconds(events: list[dict], needle: str, t0: int, t1: int) -> float:
    """Device seconds of programs whose name contains ``needle`` and that
    start inside the window, averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    tot = sum(e["dur_ns"] for e in events
              if e["line"] == MODULES_LINE and needle in e["name"]
              and t0 <= e["start_ns"] < t1)
    return tot / len(planes) / 1e9


_HLO = re.compile(r"^(%?[\w.\-]+) = .*?\b([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...), kind=...`` -> ``%fusion.12 fusion``:
    the instruction and its opcode, without the shapes."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def top_ops(events: list[dict], t0: int, t1: int, n: int = 10) -> list:
    """[[op name, device seconds]] of the ``n`` operations that took most
    time in the window, summed over occurrences, averaged over planes."""
    planes = device_planes(events)
    tot: dict[str, int] = defaultdict(int)
    for e in events:
        if e["line"] == OPS_LINE and t0 <= e["start_ns"] < t1:
            tot[op_label(e["name"])] += e["dur_ns"]
    k = max(len(planes), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def idle_gaps(events: list[dict], t0: int, t1: int, n: int = 10) -> list:
    """[[host activity, seconds]]: the device's idle time in the window on
    the first device plane, grouped by what covered the middle of each gap:
    a device program still running (gaps between its own operations), else
    the benchmark span the host was in (the most recently started one that
    covers it); largest total first, ``n`` groups at most."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = busy_intervals(events, planes[0], t0, t1)
    gaps, cur = [], t0
    for s, f in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, f)
    if t1 > cur:
        gaps.append((cur, t1))
    spans = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                   for e in events if e["name"].startswith(SPAN_PREFIX)
                   and e["name"] != WINDOW_SPAN)
    starts = [a for a, _, _ in spans]
    progs = _union([(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
                    if e["plane"] == planes[0] and e["line"] == MODULES_LINE])
    pstarts = [a for a, _ in progs]
    tot: dict[str, int] = defaultdict(int)
    cnt: dict[str, int] = defaultdict(int)
    for s, f in gaps:
        mid = (s + f) // 2
        name = "no bench span"
        k = bisect.bisect_right(pstarts, mid) - 1
        if k >= 0 and progs[k][1] >= mid:
            tot["inside a device program"] += f - s
            cnt["inside a device program"] += 1
            continue
        # the latest-started spans first; the harness's spans nest shallowly
        for k in range(bisect.bisect_right(starts, mid) - 1,
                       max(bisect.bisect_right(starts, mid) - 9, -1), -1):
            if spans[k][1] >= mid:
                name = spans[k][2]
                break
        tot[name] += f - s
        cnt[name] += 1
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[f"{name} x{cnt[name]}", ns / 1e9] for name, ns in best]


def window(events: list[dict], span: str = WINDOW_SPAN) -> tuple[int, int]:
    """The traced window: the host span the harness opens around it."""
    w = [e for e in events if e["name"] == span]
    if not w:
        raise ValueError(f"no {span!r} span in the trace")
    e = max(w, key=lambda e: e["dur_ns"])
    return e["start_ns"], e["start_ns"] + e["dur_ns"]


def reduce(events: list[dict], step_program: str) -> dict:
    """Everything the metrics read from one trace."""
    t0, t1 = window(events)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_seconds(events, t0, t1),
        "step_device_s": program_seconds(events, step_program, t0, t1),
        "device_planes": len(device_planes(events)),
        "device_ops": top_ops(events, t0, t1),
        "idle_gaps": idle_gaps(events, t0, t1),
    }
