"""The readers of the program's host-prep spans and of the idle time no
span names, on synthetic span records and a synthetic plain-form trace."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness, spec  # noqa: E402
from chipbench import trace as xt  # noqa: E402

MS = 1e6  # ns
NEW = ["schedule_s", "chunk_s", "h2d_gb", "h2d_s", "idle_unspanned_pct"]


def rec(name, dur, **attrs):
    out = {"name": name, "dur": dur}
    if attrs:
        out["attrs"] = attrs
    return out


#: two solves' worth of span records, as repro.obs emits them
SPANS = [rec("spgemm.tile", 9.0), rec("spgemm.host_prep", 3.0),
         rec("spgemm.bcsr", 0.5, nnzb=10, bs=32),
         rec("spgemm.schedule", 2.0, entries=100),
         rec("spgemm.schedule", 3.0, entries=100),
         rec("spgemm.chunk", 0.25, chunks=1),
         rec("spgemm.chunk", 0.75, chunks=1),
         rec("spgemm.h2d", 0.1, bytes=3e9),
         rec("spgemm.h2d", 0.3, bytes=1e9)]


def synthetic():
    """Window 0-100 ms.  Device ops 10-20 and 15-30 (overlapping), 50-60.
    Host: the route span spgemm.tile over the whole window; spgemm.schedule
    30-40 and spgemm.chunk 35-50 (overlapping); spgemm.bcsr 60-70 with
    spgemm.h2d 62-66 inside it; the harness's own relabel 0-10, which is
    no program span."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["fusion.1", 10 * MS, 10 * MS], ["fusion.2", 15 * MS, 15 * MS],
            ["copy.3", 50 * MS, 10 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            [xt.WINDOW, 0.0, 100 * MS], ["relabel", 0.0, 10 * MS],
            ["spgemm.tile", 0.0, 100 * MS],
            ["spgemm.schedule", 30 * MS, 10 * MS],
            ["spgemm.chunk", 35 * MS, 15 * MS],
            ["spgemm.bcsr", 60 * MS, 10 * MS],
            ["spgemm.h2d", 62 * MS, 4 * MS]]}]}]}


def readings(spans, t, solves=2):
    return harness.Readings(
        solves=solves, spans=spans, setup_spans=[], compiles=0, trace=t,
        window=xt.window(t) if t else None, work=None, peaks=None)


def read(name, r):
    return spec.metric_reader(spec.BENCH_DIR, name)(r)


def test_span_readers_per_solve():
    r = readings(SPANS, synthetic())
    assert read("schedule_s", r) == pytest.approx(2.5)
    assert read("chunk_s", r) == pytest.approx(0.5)
    assert read("h2d_gb", r) == pytest.approx(2.0)
    assert read("h2d_s", r) == pytest.approx(0.2)


def test_idle_unspanned_counts_neither_routes_nor_nesting_twice():
    """Busy 10-30 and 50-60; spanned 30-50 and 60-70.  The route span
    spgemm.tile would cover everything and does not count; the nested h2d
    adds nothing to its bcsr; relabel is no program span.  Left: 0-10 and
    70-100, 40% of the window, against 70% device idle."""
    r = readings(SPANS, synthetic())
    assert read("idle_unspanned_pct", r) == pytest.approx(40.0)
    assert read("device_idle_pct", r) == pytest.approx(70.0)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_without_their_inputs(name):
    """No spans of the program (the parent commit, or another route), no
    trace at all, or a trace with no device plane (a host platform):
    the metric is left out, never raised on."""
    t = synthetic()
    no_device = {"planes": [p for p in t["planes"]
                            if p["name"] == xt.HOST_PLANE]}
    for spans, trace in (([], t), ([], None), ([rec("plan.build", 1.0)], t)):
        assert read(name, readings(spans, trace)) is None
    if name in ("h2d_gb", "h2d_s", "idle_unspanned_pct"):
        assert read(name, readings(SPANS, no_device)) is None
        assert read(name, readings(SPANS, None)) is None
    if name == "idle_unspanned_pct":
        # spans recorded but not mirrored on the host plane
        bare = {"planes": [t["planes"][0], {
            "name": xt.HOST_PLANE, "lines": [{"name": "python3", "events": [
                [xt.WINDOW, 0.0, 100 * MS]]}]}]}
        assert read(name, readings(SPANS, bare)) is None
