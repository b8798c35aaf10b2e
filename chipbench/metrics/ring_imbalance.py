"""The busiest chip's block-kernel seconds over the mean of the chips'
(``chipbench/mesh.py``): 1.0 where the ring's partition gives every chip
the same kernel time."""
from chipbench import mesh


def read(r):
    per_chip = mesh.kernel_seconds(r)
    if not per_chip or not sum(per_chip):
        return None
    return max(per_chip) / (sum(per_chip) / len(per_chip))
