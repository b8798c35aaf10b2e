"""Share of the traced window in which no operation ran on the chip and
no program span was open on the host: the idle time that no span of the
program names.  ``device_idle_pct`` minus this is the idle time the
spans name.

repro.obs mirrors each span as a profiler annotation of the same name on
the trace's host plane; a host event counts as a program span when its
name is among the window's span records.  The route spans
(``spgemm.tile``, ``spgemm.row``, ``spgemm.dist``) enclose a whole
product, device work included, and would name everything, so they do
not count.  Averaged over the chips the cell uses, as
``device_idle_pct`` is.  None where the host plane holds no program
span (a program whose spans are not mirrored)."""
from chipbench import trace

#: spans around a whole route, open through its device work too
ROUTES = {"spgemm.tile", "spgemm.row", "spgemm.dist"}


def read(r):
    planes = r.planes()
    if not planes:
        return None
    names = {s["name"] for s in r.spans} - ROUTES
    spans = [(s, s + d) for n, s, d in trace.host_events(r.trace)
             if n in names]
    if not spans:
        return None
    covered = [sum(e - s for s, e in
                   trace.union(trace.busy(p, r.window) + spans, r.window))
               for p in planes]
    span = r.window[1] - r.window[0]
    return 100.0 * (1.0 - sum(covered) / len(covered) / span)
