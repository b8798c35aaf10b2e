"""The readers of the four-chip ring cell's device and span metrics, on a
synthetic trace of four device planes."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness, mesh, spec  # noqa: E402
from chipbench import trace as xt  # noqa: E402

MS = 1e6  # ns
KERNEL = ('%body.3 = f32[89331,8,128] custom-call(s32[16384] %b.1), '
          'custom_call_target="tpu_custom_call"')
LOOP = "%while.2 = (s32[], f32[89331,8,128]) while((s32[]) %tuple.4)"
START = ("%collective-permute-start.1 = (f32[89331,8,128], f32[89331,8,128])"
         " collective-permute-start(f32[89331,8,128] %x), channel_id=3")
DONE = ("%collective-permute-done.1 = f32[89331,8,128] "
        "collective-permute-done((f32[89331,8,128]) %collective-permute-"
        "start.1)")


def fusion(k):
    return f"%fusion.{k} = f32[8] fusion(f32[8] %p), kind=kLoop"


def four_chips():
    """Window 0-100 ms, two solves.  On chip k: a while loop over the whole
    window (it encloses the rest and never counts as running); the block
    kernel from 10 ms for 40 ms on chip 0 and 20 ms on the others; a
    collective-permute started at 60 ms and done from 75 to 80 ms, in
    flight 60-80 ms; a fusion from 65 ms for 5 + k ms.  So the permute
    is exposed 15 - k ms on chip k."""
    planes = []
    for k, kernel_ms in enumerate([40, 20, 20, 20]):
        ops = [[LOOP, 0.0, 100 * MS], [KERNEL, 10 * MS, kernel_ms * MS],
               [START, 60 * MS, 1 * MS], [fusion(k), 65 * MS, (5 + k) * MS],
               [DONE, 75 * MS, 5 * MS]]
        planes.append({"name": f"/device:TPU:{k}",
                       "lines": [{"name": xt.OPS_LINE, "events": ops}]})
    planes.append({"name": "/device:TPU:0 SparseCore", "lines": []})
    planes.append({"name": xt.HOST_PLANE, "lines": [
        {"name": "python3", "events": [[xt.WINDOW, 0.0, 100 * MS]]}]})
    return {"planes": planes}


def readings(t, setup_spans=()):
    return harness.Readings(solves=2, spans=[], setup_spans=list(setup_spans),
                            compiles=0, trace=t, window=xt.window(t),
                            work={"flops": 2, "bytes": 819e6},
                            peaks={"flops_per_s": 197e12,
                                   "bytes_per_s": 819e9})


def read(name, r):
    return spec.metric_reader(spec.BENCH_DIR, name)(r)


def test_ring_readers_on_four_planes():
    r = readings(four_chips())
    assert len(r.planes()) == 4
    assert mesh.kernel_seconds(r) == pytest.approx([0.02, 0.01, 0.01, 0.01])
    assert read("ring_kernel_s", r) == pytest.approx(0.02)
    # 1 ms of bytes at one chip's peak, 0.25 ms at four, over 20 ms
    assert read("ring_roofline_pct", r) == pytest.approx(1.25)
    assert read("ring_imbalance", r) == pytest.approx(0.02 / 0.0125)
    # exposed 15, 14, 13, 12 ms: a mean of 13.5 ms over two solves
    assert read("collective_exposed_s", r) == pytest.approx(0.00675)


def test_ring_readers_find_nothing_without_the_ring():
    """A one-chip trace with neither the kernel nor a collective: every
    reader returns None, and so does ring_prep_s without its span."""
    t = four_chips()
    t["planes"] = [{"name": "/device:TPU:0", "lines": [
        {"name": xt.OPS_LINE, "events": [[fusion(1), 0.0, 5 * MS]]}]}] \
        + [p for p in t["planes"] if p["name"] == xt.HOST_PLANE]
    r = readings(t, setup_spans=[{"name": "plan.build", "dur": 1.0}])
    for name in ("ring_kernel_s", "ring_roofline_pct", "ring_imbalance",
                 "collective_exposed_s", "ring_prep_s"):
        assert read(name, r) is None, name


def test_ring_prep_s_sums_the_setup_span():
    spans = [{"name": "spgemm.ring_prep", "dur": 2.5},
             {"name": "spgemm.schedule", "dur": 2.0}]
    assert read("ring_prep_s", readings(four_chips(), spans)) == 2.5


def test_permutes_in_flight_pair_first_started_first_done():
    """Two permutes in flight at once (the ring's values and pattern): the
    first done closes the first start.  A synchronous permute counts for
    its own event."""
    sync = "%cp.9 = f32[8] collective-permute(f32[8] %x), channel_id=5"
    plane = {"name": "/device:TPU:0", "lines": [{"name": xt.OPS_LINE,
                                                 "events": [
        [START, 10 * MS, 1 * MS], [START.replace(".1 ", ".2 "), 12 * MS,
                                   1 * MS],
        [DONE, 30 * MS, 2 * MS], [DONE, 40 * MS, 2 * MS],
        [sync, 50 * MS, 3 * MS]]}]}
    assert mesh.permute_intervals(plane) == [
        (10 * MS, 32 * MS), (12 * MS, 42 * MS), (50 * MS, 53 * MS)]
    assert mesh.exposed_seconds(plane, (0.0, 100 * MS)) == pytest.approx(
        0.035)
    assert mesh.opcode(LOOP) == "while"
    assert mesh.opcode(KERNEL) == "custom-call"
