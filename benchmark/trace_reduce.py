"""From a profiler trace to numbers.

``_line_self_times`` and the interval union are copied from
``scripts/trace_summary.py`` (tested there by ``tests/test_trace_summary``
and here by ``benchmark/tests``), so that the reduction is the benchmark's
own. ``load`` reads the newest ``.xplane.pb`` under a directory through
``jax.profiler.ProfileData`` into plain lists; everything after it works
on those lists and is tested on a small recorded one.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

SYNC_NAME = "bench_clock_sync"
#: planes whose events are operations on a chip
DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, int, int]  # name, start_ns, duration_ns


def _line_self_times(events: List[Event]) -> Dict[str, int]:
    """Per-name SELF time on one trace line: each event's duration minus
    the durations of the events nested directly inside it."""
    self_ns: Dict[str, int] = defaultdict(int)
    stack: List[List[Any]] = []  # [end_ns, name, duration_ns, child_ns]

    def close(frame):
        _end, name, dur, child_ns = frame
        self_ns[name] += max(dur - child_ns, 0)
        if stack:
            stack[-1][3] += dur

    for name, start, dur in sorted(events, key=lambda e: (e[1], -(e[1] + e[2]))):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        stack.append([start + dur, name, dur, 0])
    while stack:
        close(stack.pop())
    return self_ns


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, merged intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def load(trace_dir: str) -> Optional[Dict[str, Any]]:
    """{"devices": {plane: {line: [Event]}}, "sync_ns": start of the
    ``bench_clock_sync`` annotation on the trace's clock, or None}."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    sync_ns = None
    shape: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PREFIX)
        lines: Dict[str, List[Event]] = {}
        shape[plane.name] = {}
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            shape[plane.name][line.name] = len(events)
            if is_device:
                lines.setdefault(line.name, []).extend(events)
            if sync_ns is None:
                sync_ns = next((start for name, start, _dur in events
                                if name == SYNC_NAME), None)
        if is_device:
            devices[plane.name] = lines
    return {"devices": devices, "sync_ns": sync_ns, "shape": shape,
            "file": paths[-1]}


def op_events(trace: Dict[str, Any]) -> Dict[str, List[Event]]:
    """Per device plane, the events that are operations on the device:
    the "XLA Ops" line, or every line where a plane has no such line."""
    out = {}
    for plane, lines in trace["devices"].items():
        if OPS_LINE in lines:
            out[plane] = lines[OPS_LINE]
        else:
            out[plane] = [e for events in lines.values() for e in events]
    return out


def clip(events: List[Event], lo: int, hi: int) -> List[Event]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def busy_seconds(trace: Dict[str, Any], lo: int, hi: int) -> Optional[float]:
    """Seconds in [lo, hi) in which an operation ran, averaged over the
    device planes; None when no device plane holds an event."""
    per_plane = []
    for events in op_events(trace).values():
        spans = union([(s, s + d) for _n, s, d in clip(events, lo, hi)])
        per_plane.append(sum(e - s for s, e in spans) / 1e9)
    per_plane = [b for b in per_plane if b > 0]
    return sum(per_plane) / len(per_plane) if per_plane else None


def window_of(trace: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    """First start and last end of any device operation."""
    starts, ends = [], []
    for events in op_events(trace).values():
        if events:
            starts.append(min(s for _n, s, _d in events))
            ends.append(max(s + d for _n, s, d in events))
    return (min(starts), max(ends)) if starts else None


def top_ops(trace: Dict[str, Any], lo: int, hi: int, n: int = 10
            ) -> List[List[Any]]:
    total: Dict[str, int] = defaultdict(int)
    for events in op_events(trace).values():
        for name, ns in _line_self_times(clip(events, lo, hi)).items():
            total[name] += ns
    planes = max(1, len(trace["devices"]))
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:64], ns / 1e9 / planes] for name, ns in top]


def idle_gaps(trace: Dict[str, Any], lo: int, hi: int,
              host_spans: List[Tuple[str, int, int]], n: int = 10
              ) -> List[List[Any]]:
    """The longest gaps in which no operation ran on the first device
    plane, each named by the host span (name, start_ns, end_ns on the
    trace's clock) that covers most of it, or "none"."""
    planes = op_events(trace)
    if not planes:
        return []
    events = planes[sorted(planes)[0]]
    busy = union([(s, s + d) for _n, s, d in clip(events, lo, hi)])
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        best, best_overlap = "none", 0
        for name, hs, he in host_spans:
            overlap = min(e, he) - max(s, hs)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        out.append([best, (e - s) / 1e9])
    return out
