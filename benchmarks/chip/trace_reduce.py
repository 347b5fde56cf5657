"""Reduction of a profiler trace (an ``.xplane.pb``) to device metrics.

Device planes are named ``/device:TPU:<n>``; the operations that ran on a
device are the events of its ``XLA Ops`` line. The benchmark's host spans
(``jax.profiler.TraceAnnotation``) are events of the same names on the
host plane, on the same clock. The traced window is the host span
``traced_window``.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "traced_window"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
TOP = 10

Interval = Tuple[float, float]


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_planes(profile) -> List:
    return [p for p in profile.planes if p.name.startswith("/device:TPU:")
            and p.name[len("/device:TPU:"):].isdigit()]


def op_events(plane) -> List[Tuple[str, float, float]]:
    """(name, start ns, end ns) of every operation on a device plane."""
    out = []
    for line in plane.lines:
        if line.name == "XLA Ops":
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
    return out


def host_spans(profile, names: Iterable[str]) -> List[Tuple[str, float, float]]:
    names = set(names)
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name in names)
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals: Sequence[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (disjoint, sorted) leaves free."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of disjoint sorted ``a`` not in disjoint sorted ``b``."""
    out = []
    for lo, hi in a:
        out.extend(gaps(clip(b, lo, hi), lo, hi))
    return out


def is_collective(name: str) -> bool:
    return any(name.startswith(c) for c in COLLECTIVES)


def reduce(profile, span_names: Iterable[str] = ()) -> Optional[Dict]:
    """Busy and idle time of the traced window, averaged over devices, the
    operations that took the most device time, the idle time by the host
    span open during it, and the time a collective ran with nothing else.
    ``None`` when the trace has no window or no device."""
    windows = host_spans(profile, [WINDOW])
    planes = device_planes(profile)
    if not windows or not planes:
        return None
    _, lo, hi = windows[0]
    spans = sorted(host_spans(profile, span_names), key=lambda s: s[1])
    by_op: Dict[str, float] = defaultdict(float)
    idle_by_span: Dict[str, float] = defaultdict(float)
    busy_ns, exposed_ns = [], []
    for plane in planes:
        ops = op_events(plane)
        busy = union(clip([(a, b) for _, a, b in ops], lo, hi))
        busy_ns.append(total(busy))
        for name, a, b in ops:
            by_op[name] += max(0.0, min(b, hi) - max(a, lo))
        coll = union(clip([(a, b) for n, a, b in ops if is_collective(n)], lo, hi))
        other = union(clip([(a, b) for n, a, b in ops if not is_collective(n)], lo, hi))
        exposed_ns.append(total(subtract(coll, other)))
        for g_lo, g_hi in gaps(busy, lo, hi):
            covered = 0.0
            for name, a, b in spans:
                if a >= g_hi:
                    break
                overlap = min(b, g_hi) - max(a, g_lo)
                if overlap > 0:
                    idle_by_span[name] += overlap
                    covered += overlap
            idle_by_span["(no span)"] += max(0.0, (g_hi - g_lo) - covered)
    n = len(planes)
    window = hi - lo
    busy = float(np.mean(busy_ns))
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(idle_by_span.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": n,
        "window_s": window * 1e-9,
        "busy_s": busy * 1e-9,
        "idle_share": 1.0 - busy / window,
        "exposed_collective_share": float(np.mean(exposed_ns)) / window,
        "device_ops": [[name, ns / n * 1e-9] for name, ns in top],
        "idle_gaps": [[name, ns / n * 1e-9] for name, ns in idle],
    }
