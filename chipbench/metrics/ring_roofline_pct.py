"""Share of ``ring_kernel_s`` that the masked product's required work
(``chipbench/work.py``) needs at the peaks of all the chips in the trace
together."""
from chipbench import work


def read(r):
    kernel_s = r.read("ring_kernel_s")
    chips = len(r.planes())
    if not kernel_s or not chips or not r.work or not r.peaks:
        return None
    least, _ = work.roofline_seconds(r.work, r.peaks)
    return 100.0 * least / chips / kernel_s
