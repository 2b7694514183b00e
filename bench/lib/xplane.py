"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time.

Device planes are named ``/device:<KIND>:<n>`` (``/device:TPU:0``); their
operations sit on the ``XLA Ops`` line, nested (a ``while`` op's event
holds its body's), so an op's time is its self time.  Busy time is the
union of those operation intervals, per device, averaged over the devices; idle gaps are
the holes in that union inside the traced stretch, each labelled with the
innermost host event (a JAX host event or a ``TraceAnnotation``) that
covers the middle of the gap.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]  # (start_ns, end_ns)

OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(log_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def op_name(name: str) -> str:
    """``%fusion.3`` of an XLA op event named by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> List[Tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def device_ops(profile) -> Dict[str, List[Tuple[str, int, int]]]:
    """``{device plane name: [(op, start_ns, end_ns), ...]}``."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = _events(line)
    return out


def host_events(profile) -> List[Tuple[str, int, int]]:
    """Host events with a duration, from every thread of the host plane."""
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend(e for e in _events(line) if e[2] > e[1])
    return out


def self_times(ops: Sequence[Tuple[str, int, int]], lo: int,
               hi: int) -> Dict[str, int]:
    """Per-op time inside ``[lo, hi]`` not covered by the ops nested in it
    (a ``while`` op's events contain its body's), by op name."""
    out: Dict[str, int] = {}
    stack: List[List] = []  # [name, start, end, child time]

    def close(entry):
        own = min(entry[2], hi) - max(entry[1], lo) - entry[3]
        key = op_name(entry[0])
        out[key] = out.get(key, 0) + max(own, 0)
        if stack:
            stack[-1][3] += min(entry[2], hi) - max(entry[1], lo)

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        if min(e, hi) <= max(s, lo):
            continue
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """Holes of a merged cover inside ``[lo, hi]``."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def covering(events: Sequence[Tuple[str, int, int]], t: int) -> str:
    """Name of the shortest host event that contains time ``t``."""
    best = None
    for name, s, e in events:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no host event"


def reduce(profile, lo: Optional[int] = None, hi: Optional[int] = None,
           top: int = 10, ignore: Sequence[str] = ()) -> Optional[Dict]:
    """Busy/idle summary of the stretch ``[lo, hi]`` (ns, profiler clock;
    default: first to last device op).  ``None`` when the trace holds no
    device operation.  Host events named in ``ignore`` (the annotation
    that marks the stretch itself) never label a gap."""
    per_device = {d: ops for d, ops in device_ops(profile).items() if ops}
    if not per_device:
        return None
    all_ops = [op for ops in per_device.values() for op in ops]
    lo = min(s for _, s, _ in all_ops) if lo is None else lo
    hi = max(e for _, _, e in all_ops) if hi is None else hi
    window_ns = max(hi - lo, 1)

    busy_ns, op_time, first_busy = [], {}, None
    for dev in sorted(per_device):
        ops = per_device[dev]
        cover = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns.append(sum(e - s for s, e in cover))
        if first_busy is None:
            first_busy = cover
        for name, t in self_times(ops, lo, hi).items():
            op_time[name] = op_time.get(name, 0) + t
    host = [e for e in host_events(profile) if e[0] not in ignore]
    holes = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])
    return {
        "devices": len(per_device),
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[covering(host, (s + e) // 2), (e - s) / 1e9]
                      for s, e in holes[:top]],
    }


def annotation_bounds(profile, name: str) -> Optional[Interval]:
    """``(start, end)`` of the first host event called ``name``."""
    for ev in host_events(profile):
        if ev[0] == name:
            return ev[1], ev[2]
    return None
