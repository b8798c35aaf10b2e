"""Host seconds of the sparse ring's ``spgemm.ring_prep`` span (repro.obs)
in set-up: the partition, the scatter maps and the ring's worklists.
Later solves of the same structure hit the ring-prep cache and build
nothing."""


def read(r):
    durs = [s["dur"] for s in r.setup_spans
            if s["name"] == "spgemm.ring_prep"]
    return sum(durs) if durs else None
