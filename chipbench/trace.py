"""Reduce a profiler trace to the benchmark's device numbers.

A trace is read once from the ``.xplane.pb`` file that ``jax.profiler``
writes, into plain data: planes, each with lines, each with events
``(name, start_ns, duration_ns)``.  Everything else here works on that
plain form, so the tests can check it on a small recorded trace.

Device planes are ``/device:TPU:<n>``.  On each, the ``XLA Ops`` line holds
one event per operation the chip ran and the ``XLA Modules`` line one per
compiled program it ran.  The host plane holds the benchmark's own
``jax.profiler.TraceAnnotation`` spans, on the same clock.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns
Interval = Tuple[float, float]            # start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: the host annotation around the whole traced window
WINDOW = "chipbench.window"


def from_xplane(path: str) -> dict:
    """Plain form of an ``.xplane.pb`` file: device planes and the host
    plane only, each line's events as ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        planes.append({"name": plane.name, "lines": [
            {"name": line.name,
             "events": [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        for ev in line.events]}
            for line in plane.lines]})
    return {"planes": planes}


def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, line: str) -> List[Event]:
    for ln in plane["lines"]:
        if ln["name"] == line:
            return [tuple(e) for e in ln["events"]]
    return []


def host_events(trace: dict) -> List[Event]:
    """Every event of every line of the host plane."""
    out: List[Event] = []
    for p in trace["planes"]:
        if p["name"] == HOST_PLANE:
            for ln in p["lines"]:
                out.extend(tuple(e) for e in ln["events"])
    return out


def window(trace: dict) -> Optional[Interval]:
    """The traced window: the host's ``WINDOW`` annotation."""
    for name, start, dur in host_events(trace):
        if name == WINDOW:
            return start, start + dur
    return None


def union(intervals: Iterable[Interval], clip: Interval) -> List[Interval]:
    """Disjoint sorted union of ``intervals``, clipped to ``clip``."""
    lo, hi = clip
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy(plane: dict, win: Interval) -> List[Interval]:
    """Intervals of ``win`` in which some operation ran on the device."""
    return union(((s, s + d) for _, s, d in line_events(plane, OPS_LINE)),
                 win)


def gaps(busy_iv: List[Interval], win: Interval) -> List[Interval]:
    """The idle intervals of ``win``: its complement of ``busy_iv``."""
    out, t = [], win[0]
    for s, e in busy_iv:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if win[1] > t:
        out.append((t, win[1]))
    return out


def events_matching(plane: dict, line: str, pattern: str) -> List[Event]:
    """Events of ``line`` whose name matches ``pattern`` (``re.search``)."""
    rx = re.compile(pattern)
    return [e for e in line_events(plane, line) if rx.search(e[0])]


def seconds(events: Iterable[Event], win: Interval) -> float:
    """Device seconds of ``events`` inside ``win``."""
    return sum(max(0.0, min(s + d, win[1]) - max(s, win[0]))
               for _, s, d in events) / 1e9


def short_name(op: str) -> str:
    """An XLA op's event name is its whole HLO instruction; keep the
    instruction's name and opcode, and a custom call's target."""
    name, _, rest = op.partition(" = ")
    code = re.search(r" ([a-z][a-z0-9-]*)\(", " " + rest)
    out = f"{name} {code.group(1)}" if code else name
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return f"{out} {target.group(1)}" if target else out


def top_ops(plane: dict, win: Interval, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` operations that took most device time in ``win``."""
    total: Dict[str, float] = collections.defaultdict(float)
    for name, s, d in line_events(plane, OPS_LINE):
        total[name] += seconds([(name, s, d)], win)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])
    return [[short_name(name), t] for name, t in ranked[:k] if t > 0]


def top_gaps(plane: dict, win: Interval, spans: List[Event], k: int = 10
             ) -> List[Tuple[str, float]]:
    """The ``k`` longest pieces of idle time in ``win``: each idle gap cut
    at the edges of the host ``spans``, each piece named by the span it
    falls in (``between phases`` where none)."""
    pieces = []
    for g0, g1 in gaps(busy(plane, win), win):
        cuts = sorted({g0, g1} | {t for _, s, d in spans
                                  for t in (s, s + d) if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [n for n, s, d in spans if s <= mid < s + d]
            pieces.append([inside[0] if inside else "between phases",
                           (b - a) / 1e9])
    pieces.sort(key=lambda p: -p[1])
    return pieces[:k]
