"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the
device operations that took most time, and the idle gaps by what the
host was doing.

The window is the host span ``bench.window``.  The harness records it
and the serve loop's other spans on its own clock, with the profiler's
host tracer off, and ``host_events`` ties them to the trace's clock at
the first program the device ran.  Device time is the union of the intervals
of the events on the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane, clipped to the window and averaged over the chips.  A gap is a
stretch of the window in which chip 0 runs no operation; it is named
after the ``bench.*`` host span that covers most of it, or ``other``.
An operation's time is its own time (the ops of a ``while`` body are
taken out of the loop's), summed by the kind of operation.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PLANE = "/host:bench"
MODULE_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over chips
    chips: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [Event(pl.name, ln.name, ev.name, ev.start_ns,
                  ev.start_ns + ev.duration_ns)
            for pl in pd.planes for ln in pl.lines for ev in ln.events]


def host_events(events: Sequence[Event],
                spans: Sequence[Tuple[str, float, float]],
                anchor_s: float) -> List[Event]:
    """The benchmark's host spans ``(name, start s, end s)`` as events on
    the trace's clock.  The clocks are tied at the device's first
    program (``XLA Modules``, else ``XLA Ops``): the window starts with
    nothing in flight, so that program is the window's first batch,
    launched as soon as the host had it on the device at ``anchor_s``.
    The tie is late by the launch's own latency, well under a
    millisecond."""
    dev = [e for e in events if DEVICE_PLANE.match(e.plane)]
    starts = ([e.start_ns for e in dev if e.line == MODULE_LINE]
              or [e.start_ns for e in dev if e.line == OP_LINE])
    if not starts:
        raise ValueError("no device program ran in the trace")
    off = min(starts) - anchor_s * 1e9
    return [Event(HOST_PLANE, "spans", name, a * 1e9 + off, b * 1e9 + off)
            for name, a, b in spans]


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge overlapping intervals, sorted by start."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_kind(name: str) -> str:
    """``%cuconv_fused.54 = f32[...] custom-call(...)`` -> ``cuconv_fused``:
    the HLO instruction's name without its number, so one kind of
    operation sums over the layers and buckets."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.(\d+|clone))+$", "", head)


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, float]]:
    """``(name, start, end)`` pieces of each event's own time: an event
    nested in another on the same line (an op in a ``while`` body)
    takes its interval out of the enclosing event's."""
    out: List[Tuple[str, float, float]] = []
    by_line: Dict[Tuple[str, str], List[Event]] = collections.defaultdict(
        list)
    for e in events:
        by_line[(e.plane, e.line)].append(e)
    for evs in by_line.values():
        evs = sorted(evs, key=lambda e: (e.start_ns, -e.end_ns))
        stack: List[List] = []          # [event, cursor]

        def close_until(t):
            while stack and stack[-1][0].end_ns <= t:
                ev, cur = stack.pop()
                if ev.end_ns > cur:
                    out.append((ev.name, cur, ev.end_ns))
                if stack:
                    stack[-1][1] = ev.end_ns
        for e in evs:
            close_until(e.start_ns)
            if stack:
                parent = stack[-1]
                if e.start_ns > parent[1]:
                    out.append((parent[0].name, parent[1], e.start_ns))
                parent[1] = e.start_ns
            stack.append([e, e.start_ns])
        close_until(float("inf"))
    return out


def _clip(a: float, b: float, t0: float, t1: float):
    a, b = max(a, t0), min(b, t1)
    return (a, b) if b > a else None


def summarize(events: Sequence[Event], top: int = 10) -> Summary:
    windows = [e for e in events if e.name == WINDOW_SPAN
               and not DEVICE_PLANE.match(e.plane)]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} host span; "
                         f"found {len(windows)}")
    t0, t1 = windows[0].start_ns, windows[0].end_ns
    per_chip: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(
        list)
    for e in events:
        if not (DEVICE_PLANE.match(e.plane) and e.line == OP_LINE):
            continue
        iv = _clip(e.start_ns, e.end_ns, t0, t1)
        if iv is not None:
            per_chip[e.plane].append(iv)
    op_time: Dict[str, float] = collections.defaultdict(float)
    for name, a, b in self_times(
            [e for e in events if DEVICE_PLANE.match(e.plane)
             and e.line == OP_LINE]):
        iv = _clip(a, b, t0, t1)
        if iv is not None:
            op_time[op_kind(name)] += (iv[1] - iv[0]) / 1e9
    if not per_chip:
        raise ValueError("no device operation ran inside the window")
    busy = {p: sum(b - a for a, b in union(iv)) / 1e9
            for p, iv in per_chip.items()}
    first = sorted(per_chip, key=lambda p: int(p.rsplit(":", 1)[1]))[0]
    gaps = []
    edge = t0
    for a, b in union(per_chip[first]) + [(t1, t1)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    host = [e for e in events if e.name.startswith("bench.")
            and e.name != WINDOW_SPAN and not DEVICE_PLANE.match(e.plane)]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        cover: Dict[str, float] = collections.defaultdict(float)
        for h in host:
            iv = _clip(h.start_ns, h.end_ns, a, b)
            if iv is not None:
                cover[h.name] += iv[1] - iv[0]
        label = max(cover, key=cover.get) if cover else "other"
        named.append((label, (b - a) / 1e9))
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(t1 - t0) / 1e9,
                   busy_s=sum(busy.values()) / len(busy),
                   chips=len(busy), device_ops=ops, idle_gaps=named)
