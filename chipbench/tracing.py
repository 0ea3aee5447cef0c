"""From a profiler trace to metrics: device busy time and idle share,
kernel time by name pattern, the device operations that took most time,
and the longest idle gaps by what the host was doing.

`load` reads the `.xplane.pb` the JAX profiler writes into a plain list
of events; everything else works on such lists, so it can be checked on
a small recorded trace and on events written by hand.

An event is (plane, line, name, start_ns, end_ns). Device operations are
the events of the `XLA Ops` line of each `/device:TPU:<n>` plane; host
spans are the benchmark's own annotations (`bench.<name>`) on the host
plane.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, int, int]
Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> List[Event]:
    """The device events and the benchmark's host spans of the trace at
    `path` (a file or a profiler log dir)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    start = int(ev.start_ns)
                    out.append((plane.name, line.name, ev.name, start,
                                start + int(ev.duration_ns)))
    return out


def device_ops(events: Iterable[Event]) -> Dict[str, List[Event]]:
    """Device operations by device plane."""
    out: Dict[str, List[Event]] = {}
    for ev in events:
        if DEVICE_PLANE.match(ev[0]) and ev[1] == OPS_LINE:
            out.setdefault(ev[0], []).append(ev)
    return out


def host_spans(events: Iterable[Event]) -> List[Event]:
    """The benchmark's host spans."""
    return [ev for ev in events
            if not DEVICE_PLANE.match(ev[0]) and ev[2].startswith(SPAN_PREFIX)]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered_ns(merged: Sequence[Interval], windows: Sequence[Interval]
               ) -> int:
    """Length of the (merged) busy intervals inside the (merged) windows."""
    total, i = 0, 0
    for ws, we in windows:
        while i < len(merged) and merged[i][1] <= ws:
            i += 1
        j = i
        while j < len(merged) and merged[j][0] < we:
            s, e = merged[j]
            total += max(0, min(e, we) - max(s, ws))
            j += 1
    return total


def busy_ns(ops: Sequence[Event], windows: Sequence[Interval]) -> int:
    """Time inside `windows` in which some operation ran on the device."""
    return covered_ns(union((e[3], e[4]) for e in ops), union(windows))


def kernel_ns(ops: Iterable[Event], patterns: Sequence[str], lo: int,
              hi: int) -> Tuple[int, int]:
    """(summed duration, count) of operations inside [lo, hi) whose name
    matches one of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    total, n = 0, 0
    for ev in ops:
        if ev[3] >= lo and ev[4] <= hi and any(r.search(ev[2]) for r in rx):
            total += ev[4] - ev[3]
            n += 1
    return total, n


def short_name(name: str) -> str:
    """An operation's HLO instruction name: `%pad.1 = f32[...] pad(...)`
    -> `pad.1`."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head[:64]


def top_ops(ops: Iterable[Event], lo: int, hi: int, k: int = 10
            ) -> List[List]:
    """[[name, seconds]] of the k operations that took most device time
    inside [lo, hi), summed by instruction name."""
    by: Dict[str, int] = {}
    for ev in ops:
        s, e = max(ev[3], lo), min(ev[4], hi)
        if e > s:
            name = short_name(ev[2])
            by[name] = by.get(name, 0) + (e - s)
    best = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(ops: Sequence[Event], spans: Sequence[Event], lo: int,
              hi: int, k: int = 10) -> List[List]:
    """[[host activity, seconds]]: the device's idle time inside [lo, hi),
    each gap given to the host span that overlaps it most (the innermost
    when spans nest: the shortest of those that overlap it equally) and
    summed by span name; `host.other` where no span overlaps. The k
    largest."""
    busy = union(clip([(e[3], e[4]) for e in ops], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted(spans, key=lambda ev: ev[3])
    by: Dict[str, int] = {}
    j = 0
    for gs, ge in gaps:
        while j < len(spans) and spans[j][3] < gs and spans[j][4] <= gs:
            j += 1
        best, best_ov, best_len = "host.other", 0, None
        for sp in spans[j:]:
            if sp[3] >= ge:
                break
            ov = min(sp[4], ge) - max(sp[3], gs)
            ln = sp[4] - sp[3]
            if ov > best_ov or (ov == best_ov and ov > 0
                                and ln < best_len):
                best, best_ov, best_len = sp[2], ov, ln
        by[best] = by.get(best, 0) + (ge - gs)
    out = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in out]


class Reduced:
    """A trace reduced for the metric readers: the traced window, the
    device operations in it, and the host spans."""

    def __init__(self, events: List[Event], window: str = "window"):
        self.ops_by_device = device_ops(events)
        self.spans = host_spans(events)
        win = [ev for ev in self.spans if ev[2] == SPAN_PREFIX + window]
        if not win:
            raise ValueError(f"no {SPAN_PREFIX}{window} span in the trace")
        self.lo, self.hi = win[0][3], win[0][4]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def devices(self) -> List[str]:
        return sorted(self.ops_by_device)

    def busy_s(self, windows: Optional[Sequence[Interval]] = None) -> float:
        """Busy seconds inside `windows` (default: the traced window),
        averaged over the chips in the trace."""
        windows = windows if windows is not None else [(self.lo, self.hi)]
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(busy_ns(self.ops_by_device[d], windows)
                   for d in devs) / len(devs) / 1e9

    def span_windows(self, name: str) -> List[Interval]:
        return [(ev[3], ev[4]) for ev in self.spans
                if ev[2] == SPAN_PREFIX + name
                and ev[3] >= self.lo and ev[4] <= self.hi]

    def kernel_s(self, patterns: Sequence[str]) -> Tuple[float, int]:
        total, n = 0, 0
        for d in self.devices():
            t, c = kernel_ns(self.ops_by_device[d], patterns, self.lo,
                             self.hi)
            total, n = total + t, n + c
        return total / 1e9, n

    def breakdown(self) -> Dict[str, List[List]]:
        ops = [ev for d in self.devices() for ev in self.ops_by_device[d]]
        inner = [ev for ev in self.spans
                 if ev[2] != SPAN_PREFIX + "window"]
        first = self.devices()[:1]
        first_ops = self.ops_by_device[first[0]] if first else []
        return {"device_ops": top_ops(ops, self.lo, self.hi),
                "idle_gaps": idle_gaps(first_ops, inner, self.lo, self.hi)}
