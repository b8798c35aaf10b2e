"""The reduction from a profiler trace to device numbers."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness, spec  # noqa: E402
from chipbench import trace as xt  # noqa: E402

#: an excerpt of a trace recorded on one v5e during a traced window of
#: ``tc.kron-s16.tile``: 0.8 s from the end of one solve's kernel into the
#: next solve's host prep, the window annotation cut to that range
RECORDED = pathlib.Path(__file__).with_name("trace_excerpt.json")

MS = 1e6  # ns


def synthetic():
    """Window 0-100 ms; ops 10-20, 15-30 (overlapping), 50-60, 95-120;
    host phases relabel 0-10 and masked_spgemm 30-50."""
    kernel = '%k = f32[8] custom-call(), custom_call_target="tpu_custom_call"'
    ops = [["fusion.1", 10 * MS, 10 * MS], [kernel, 15 * MS, 15 * MS],
           [kernel, 50 * MS, 10 * MS], ["copy.2", 95 * MS, 25 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ["jit__block_spgemm_pallas(7)", 10 * MS, 50 * MS]]}]},
        {"name": "/device:TPU:0 SparseCore", "lines": []},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            [xt.WINDOW, 0.0, 100 * MS], ["relabel", 0.0, 10 * MS],
            ["masked_spgemm", 30 * MS, 20 * MS]]}]}]}


def test_busy_gaps_and_labels_on_a_synthetic_trace():
    t = synthetic()
    planes = xt.device_planes(t)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    win = xt.window(t)
    assert win == (0.0, 100 * MS)
    busy = xt.busy(planes[0], win)
    assert busy == [(10 * MS, 30 * MS), (50 * MS, 60 * MS),
                    (95 * MS, 100 * MS)]
    assert xt.gaps(busy, win) == [(0.0, 10 * MS), (30 * MS, 50 * MS),
                                  (60 * MS, 95 * MS)]
    spans = [e for e in xt.host_events(t) if e[0] in harness.PHASES]
    assert xt.top_gaps(planes[0], win, spans) == [
        ["between phases", 0.035], ["masked_spgemm", 0.02],
        ["relabel", 0.01]]
    assert xt.top_ops(planes[0], win) == [
        ["%k custom-call tpu_custom_call", 0.025], ["fusion.1", 0.01],
        ["copy.2", 0.005]]
    kernel = xt.events_matching(planes[0], xt.OPS_LINE, "tpu_custom_call")
    assert xt.seconds(kernel, win) == pytest.approx(0.025)


def test_short_names_of_recorded_ops():
    kernel = ("%body.3 = f32[142085,32,32]{2,1,0:T(8,128)} custom-call("
              "s32[16384]{0:T(1024)S(1)} %bitcast.15, f32[142085,32,32]"
              "{2,1,0:T(8,128)} %get-tuple-element.90), custom_call_target="
              "\"tpu_custom_call\", output_to_operand_aliasing={{}: (6, {})}")
    loop = ("%while = (s32[]{:T(128)}, f32[142085,32,32]{2,1,0:T(8,128)}) "
            "while((s32[]{:T(128)}, f32[142085,32,32]{2,1,0:T(8,128)}) "
            "%tuple.15), condition=%wide.region_3.4")
    assert xt.short_name(kernel) == "%body.3 custom-call tpu_custom_call"
    assert xt.short_name(loop) == "%while while"
    assert xt.short_name("fusion.1") == "fusion.1"


def _readings(t, solves=1):
    win = xt.window(t)
    return harness.Readings(solves=solves, spans=[], setup_spans=[],
                            compiles=0, trace=t, window=win,
                            work={"flops": 2, "bytes": 819e6},
                            peaks={"flops_per_s": 197e12,
                                   "bytes_per_s": 819e9})


def test_metric_readers_on_a_synthetic_trace():
    r = _readings(synthetic(), solves=2)
    read = lambda name: spec.metric_reader(spec.BENCH_DIR, name)(r)  # noqa
    assert read("device_idle_pct") == pytest.approx(65.0)
    assert read("tile_kernel_s") == pytest.approx(0.0125)
    # 1 ms of bytes at peak over 12.5 ms of kernel per solve
    assert read("tile_roofline_pct") == pytest.approx(8.0)
    assert read("row_kernel_s") is None
    assert read("row_roofline_pct") is None


def test_reduction_on_a_recorded_trace():
    """Invariants on a real trace: busy and idle tile the window, no
    operation runs inside an idle gap, and the kernel's events are found
    by name."""
    t = json.loads(RECORDED.read_text())
    win = xt.window(t)
    planes = xt.device_planes(t)
    assert win is not None and len(planes) == 1
    busy = xt.busy(planes[0], win)
    idle = xt.gaps(busy, win)
    span = win[1] - win[0]
    assert sum(e - s for s, e in busy) + sum(e - s for s, e in idle) \
        == pytest.approx(span)
    for _, s, d in xt.line_events(planes[0], xt.OPS_LINE):
        for g0, g1 in idle:   # an op of zero length keeps nothing busy
            assert d == 0 or s + d <= g0 or s >= g1
    total_ops = sum(d for _, _, d in xt.line_events(planes[0], xt.OPS_LINE))
    assert sum(e - s for s, e in busy) <= total_ops
    r = _readings(t)
    kernel_s = spec.metric_reader(spec.BENCH_DIR, "tile_kernel_s")(r)
    assert kernel_s is not None and 0 < kernel_s <= span / 1e9
    idle_pct = spec.metric_reader(spec.BENCH_DIR, "device_idle_pct")(r)
    assert 0 < idle_pct < 100
