"""Host time per solve inside the program's ``spgemm.host_prep`` spans
(repro.obs): padding the operands on the row route, BCSR conversion on the
tile route.  On the tile route the span leaves out the worklist build
(``build_spgemm_schedule``) and its chunking (``chunk_schedule``)."""


def read(r):
    durs = [s["dur"] for s in r.spans if s["name"] == "spgemm.host_prep"]
    if not durs:
        return None
    return sum(durs) / r.solves
