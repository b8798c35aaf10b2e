"""Host seconds per solve in the program's ``spgemm.schedule`` spans
(repro.obs): the tile route's worklist build, ``build_spgemm_schedule``
in ``kernels/masked_matmul/ops.py``."""


def read(r):
    durs = [s["dur"] for s in r.spans if s["name"] == "spgemm.schedule"]
    if not durs:
        return None
    return sum(durs) / r.solves
