"""Share of the row program's device time that the masked product's
required work needs at the chip's peaks (``chipbench/work.py``)."""
from chipbench import work


def read(r):
    kernel_s = r.read("row_kernel_s")
    if not kernel_s or not r.work or not r.peaks:
        return None
    least, _ = work.roofline_seconds(r.work, r.peaks)
    return 100.0 * least / kernel_s
