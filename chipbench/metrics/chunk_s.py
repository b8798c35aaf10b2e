"""Host seconds per solve in the program's ``spgemm.chunk`` spans
(repro.obs): the Pallas executor splitting the tile route's worklist into
kernel calls, ``chunk_schedule``, once for each replay (values and
structure)."""


def read(r):
    durs = [s["dur"] for s in r.spans if s["name"] == "spgemm.chunk"]
    if not durs:
        return None
    return sum(durs) / r.solves
