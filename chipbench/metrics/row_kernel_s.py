"""Device seconds per solve of the vmapped row program
(``core/masked_spgemm.py`` ``_masked_spgemm_padded``): the events of the
trace's ``XLA Modules`` line that carry its jitted name."""
from chipbench import trace

#: the name the program's events carry in the device trace
PROGRAM = r"_masked_spgemm_padded"


def read(r):
    return r.device_seconds(trace.MODULES_LINE, PROGRAM)
