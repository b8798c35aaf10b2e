"""Device numbers of a program that runs on several chips at once.

The sparse ring (``core/distributed.py``) runs one program on every chip
of the mesh: each chip replays its own worklists on the block kernel and
rotates B's K-slabs to its neighbour by collective-permutes.  Its stages
end together, so the chip with the most kernel time sets the pace, and a
collective is exposed where a chip waits on it with nothing else to do.
Both are read per chip here, from the plain trace of ``trace.py``.
"""
from __future__ import annotations

import re
from typing import List

from chipbench import trace

#: the Pallas block kernel's events (its ``pallas_call`` carries no name;
#: on the ring it is the only Mosaic kernel)
KERNEL = r'custom_call_target="tpu_custom_call"'
#: an op's opcode in its event name, which is its HLO instruction
OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
#: ops that only enclose others: a while loop runs through its whole body
CONTAINERS = {"while", "conditional", "call"}
PERMUTE = "collective-permute"


def opcode(name: str) -> str:
    _, _, rest = name.partition(" = ")
    code = OPCODE.search(" " + rest)
    return code.group(1) if code else ""


def kernel_seconds(r) -> List[float]:
    """Device seconds per solve of the block kernel on each chip; empty
    where no chip ran it."""
    planes = r.planes()
    events = [trace.events_matching(p, trace.OPS_LINE, KERNEL)
              for p in planes]
    if not any(events):
        return []
    return [trace.seconds(ev, r.window) / r.solves for ev in events]


def permute_intervals(plane: dict) -> List[trace.Interval]:
    """When a collective-permute is in flight on ``plane``: from each
    ``collective-permute-start`` to the end of the ``-done`` that closes
    it, first started first done; a synchronous permute, or a done with
    no start, for as long as its own event."""
    out, open_starts = [], []
    for name, s, d in sorted(trace.line_events(plane, trace.OPS_LINE),
                             key=lambda e: e[1]):
        code = opcode(name)
        if code == PERMUTE + "-start":
            open_starts.append(s)
        elif code == PERMUTE + "-done":
            out.append((open_starts.pop(0) if open_starts else s, s + d))
        elif code == PERMUTE:
            out.append((s, s + d))
    return out


def exposed_seconds(plane: dict, win: trace.Interval) -> float:
    """Seconds of ``win`` in which a collective-permute is in flight on
    ``plane`` and no other op runs (ops that only enclose others, such as
    a while loop, do not count as running)."""
    flight = trace.union(permute_intervals(plane), win)
    others = trace.union(
        ((s, s + d) for name, s, d in trace.line_events(plane, trace.OPS_LINE)
         if opcode(name) not in CONTAINERS
         and not opcode(name).startswith(PERMUTE)), win)
    return (_length(trace.union(flight + others, win))
            - _length(others)) / 1e9


def _length(intervals: List[trace.Interval]) -> float:
    return sum(e - s for s, e in intervals)
