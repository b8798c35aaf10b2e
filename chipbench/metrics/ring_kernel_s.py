"""Device seconds per solve of the sparse ring's block kernel
(``kernels/masked_matmul/kernel.py`` ``block_spgemm_kernel``, replayed by
``core/distributed.py``) on the busiest of the cell's chips: the ring's
stages end together, so that chip sets the pace."""
from chipbench import mesh


def read(r):
    per_chip = mesh.kernel_seconds(r)
    return max(per_chip) if per_chip else None
