"""Seconds per solve in which a collective-permute (the ring's K-slab
rotation) is in flight on a chip and no other op runs there, averaged
over the chips (``chipbench/mesh.py``).  None where no chip ran one."""
from chipbench import mesh


def read(r):
    planes = r.planes()
    if not any(mesh.permute_intervals(p) for p in planes):
        return None
    exposed = [mesh.exposed_seconds(p, r.window) for p in planes]
    return sum(exposed) / len(exposed) / r.solves
