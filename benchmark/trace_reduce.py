"""Reduction from a ``jax.profiler`` trace to the device numbers.

Works on plain data so that it can be checked on a small recorded trace
(``tests/data/small_trace.json``): a list of planes, each ``{"name",
"lines": [{"name", "events": [[name, start_ns, duration_ns], ...]}]}``.
``load_xplane`` turns an ``.xplane.pb`` into that with nothing but jax.

A device plane is one named ``/device:TPU:<n>`` (any ``/device:`` plane
that is not a host); its operations are the events of its "XLA Ops" line.
A while loop's event contains its body's events on the same line, so
*busy* is the union of the intervals and an operation's time in the
breakdown is its self time (its span minus what its children cover).
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns


def load_xplane(trace_dir: str) -> List[dict]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [[e.name, float(e.start_ns),
                                      float(e.duration_ns)]
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: List[dict]) -> List[dict]:
    return [p for p in planes
            if p["name"].startswith("/device:") and "host" not in p["name"].lower()
            and any(ln["events"] for ln in p["lines"])]


def op_events(plane: dict) -> List[Event]:
    lines = [ln for ln in plane["lines"] if ln["name"] == "XLA Ops"]
    if not lines:
        skip = ("Steps", "XLA Modules", "XLA TraceMe", "Framework")
        lines = [ln for ln in plane["lines"]
                 if not any(s in ln["name"] for s in skip)]
    return sorted((tuple(e) for ln in lines for e in ln["events"]
                   if e[2] > 0), key=lambda e: (e[1], -e[2]))


def union_intervals(events: List[Event]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for _n, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds by operation name, children's time taken off the parent."""
    total: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, self_ns = stack.pop()
            total[name] = total.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for name, s, d in events:  # sorted by start, longer first
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    close(float("inf"))
    return total


def host_events(planes: List[dict]) -> List[Event]:
    return sorted((tuple(e) for p in planes if p["name"].startswith("/host:")
                   for ln in p["lines"] for e in ln["events"] if e[2] > 0),
                  key=lambda e: e[1])


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), kind=kOutput`` -> ``fusion.12``:
    XLA's own name of the operation, without its HLO text."""
    return name.split(" = ", 1)[0].strip().lstrip("%")[:80]


def _name_gap(a: float, b: float, host: List[Event], before: str,
              after: str) -> str:
    """What the host was doing in [a, b): the host event that covers most
    of it, else the device operations on either side."""
    best, best_cover = None, 0.0
    for name, s, d in host:
        if s >= b:
            break
        cover = min(b, s + d) - max(a, s)
        if cover > best_cover:
            best, best_cover = name, cover
    if best is not None and best_cover >= 0.5 * (b - a):
        return f"host:{short_name(best)}"
    return f"between:{short_name(before)}|{short_name(after)}"


def reduce_trace(planes: List[dict], top: int = 10) -> Optional[dict]:
    """``{"busy_s", "window_s", "idle_share", "device_ops", "idle_gaps",
    "chips"}`` or None when no device operation was recorded. The window
    is the traced span as the profile holds it: from the first to the last
    event, the host threads' and the device operations' alike, so that
    what the device idles at the head and the tail of the span, while
    only the host works, counts as idle. Busy is averaged over the chips."""
    devs = device_planes(planes)
    per = [(p["name"], op_events(p)) for p in devs]
    per = [(n, ev) for n, ev in per if ev]
    if not per:
        return None
    host = host_events(planes)
    t0 = min([ev[0][1] for _n, ev in per] + [e[1] for e in host[:1]])
    t1 = max([s + d for _n, ev in per for _x, s, d in ev]
             + [s + d for _x, s, d in host])
    window = (t1 - t0) / 1e9
    if window <= 0:
        return None
    busy = []
    ops: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for _pname, ev in per:
        spans = union_intervals(ev)
        busy.append(sum(b - a for a, b in spans) / 1e9)
        for name, sec in self_times(ev).items():
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + sec / len(per)
        starts = [e[1] for e in ev]
        # Only the longest gaps are named (there are as many gaps as
        # operations, nearly all of them microseconds long).
        # The span's head and tail, where only the host works, are gaps too.
        edges = [(t0, t0)] + spans + [(t1, t1)]
        longest = sorted(((b0 - a1, a1, b0) for (_a0, a1), (b0, _b1)
                          in zip(edges, edges[1:]) if b0 > a1),
                         reverse=True)[:4 * top]
        for _len, a1, b0 in longest:
            i = bisect.bisect_left(starts, b0)
            after = ev[min(i, len(ev) - 1)][0]
            before = ev[max(bisect.bisect_left(starts, a1) - 1, 0)][0]
            gaps.append((_name_gap(a1, b0, host, before, after),
                         (b0 - a1) / 1e9))
    busy_s = sum(busy) / len(busy)
    # The longest gaps, summed by name.
    by_name: Dict[str, float] = {}
    for name, sec in gaps:
        by_name[name] = by_name.get(name, 0.0) + sec
    return {
        "busy_s": busy_s,
        "window_s": window,
        "idle_share": 1.0 - busy_s / window,
        "chips": len(per),
        "device_ops": sorted(([n, s] for n, s in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s] for n, s in by_name.items()),
                            key=lambda x: -x[1])[:top],
    }
