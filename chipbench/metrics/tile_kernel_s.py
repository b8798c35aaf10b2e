"""Device seconds per solve of the tile route's Pallas kernel
(``kernels/masked_matmul/kernel.py`` ``block_spgemm_kernel``): the
Mosaic custom calls on the trace's ``XLA Ops`` line.  Its ``pallas_call``
carries no name, so its events are found by their custom-call target;
on this route it is the only Mosaic kernel."""
from chipbench import trace

#: what the kernel's events carry in the device trace
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(r):
    return r.device_seconds(trace.OPS_LINE, KERNEL)
