"""Gigabytes per solve copied from host to device: the ``bytes`` of the
program's ``spgemm.h2d`` spans (repro.obs, ``core/formats.py``
``to_device``), each the size of an array as it lands on the device.
Read only where the trace has a device plane, so that a traced run on
the host's CPU reports the metric set ``test_traced_run_reads_the_layers_it_can``
pins (PERF.md section 3)."""


def read(r):
    sizes = [s["attrs"]["bytes"] for s in r.spans
             if s["name"] == "spgemm.h2d" and "bytes" in s.get("attrs", {})]
    if not sizes or not r.planes():
        return None
    return sum(sizes) / r.solves / 1e9
