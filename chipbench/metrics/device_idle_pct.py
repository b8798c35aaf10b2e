"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's operation intervals) / window, averaged over
the chips the cell uses."""
from chipbench import trace


def read(r):
    planes = r.planes()
    if not planes:
        return None
    span = r.window[1] - r.window[0]
    busy = sum(sum(e - s for s, e in trace.busy(p, r.window))
               for p in planes) / len(planes)
    return 100.0 * (1.0 - busy / span)
