"""Host seconds per solve in the program's ``spgemm.h2d`` spans
(repro.obs, ``core/formats.py`` ``to_device``): the host's part of each
host-to-device copy.  A copy may return before its transfer ends, so
this is the time the host spends, not the transfer's.  Read only where
the trace has a device plane, as ``h2d_gb`` is, and for its reason."""


def read(r):
    durs = [s["dur"] for s in r.spans if s["name"] == "spgemm.h2d"]
    if not durs or not r.planes():
        return None
    return sum(durs) / r.solves
