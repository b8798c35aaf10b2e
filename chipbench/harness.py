"""One run of one cell: set-up, a measured window of solves, the check.

A solve is what a user of triangle counting runs: from the host CSR
adjacency to an exact count, through the program's own entry points.

1. ``degree_relabel`` and ``tril`` (``relabel``);
2. ``masked_spgemm(L, L, L, **traffic options)`` (``masked_spgemm``);
3. each edge's value, presence and column read out of the mask-aligned
   result, and the count summed from them exactly (``count``).

Set-up generates the input from the seed and runs one solve, which
compiles or loads every program the window runs.  The window then starts
solves back to back until ``seconds`` have passed and closes when the last
one finishes.  After the window, and outside every timed number, the plain
reference checks every solve of the window edge by edge.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import graphs, reference, spec, work
from chipbench import trace as xtrace

#: the numbers compared with the reference, and their limits.  Counts and
#: supports are integers that float32 holds exactly, so both comparisons
#: are exact: any difference fails.
LIMITS = {"count_gap": 0, "edge_gap": 0}
#: the host annotations around a solve's phases, in the profiler's trace
PHASES = ("relabel", "masked_spgemm", "count")
#: jax.monitoring's event for a program compiled or loaded from the cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


class DeviceError(Exception):
    """The run is not on a chip the cell and the peaks table allow."""


def check_device(chips: int, peaks: dict) -> dict:
    """The device as JAX reports it; raises ``DeviceError`` unless it is a
    TPU in ``peaks`` with at least ``chips`` chips."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise DeviceError(f"no TPU: JAX found platform {d0.platform!r}")
    if len(devs) < chips:
        raise DeviceError(f"{chips} chips needed, JAX found {len(devs)}")
    if d0.device_kind not in peaks:
        raise DeviceError(f"device kind {d0.device_kind!r} is not in "
                          f"peaks.json")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


@dataclasses.dataclass
class Output:
    """What one solve produced: the program's ``L`` and, per edge of it in
    row-major order, value, presence and column from the result."""

    indptr: np.ndarray
    indices: np.ndarray
    values: np.ndarray
    present: np.ndarray
    cols: np.ndarray
    count: float


def _per_edge(vals, present, mask_cols, rows, slots):
    return vals[rows, slots], present[rows, slots], mask_cols[rows, slots]


class Solver:
    """The solve the window drives, on one input adjacency."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray,
                 options: dict):
        import jax
        from repro.core.formats import CSR
        n = len(indptr) - 1
        self.adj = CSR(indptr, indices, np.ones(len(indices), np.float32),
                       (n, n))
        self.options = dict(options)
        self._per_edge = jax.jit(_per_edge)

    def __call__(self) -> Output:
        import jax
        from jax.profiler import TraceAnnotation
        from repro.core import masked_spgemm
        from repro.core.formats import tril
        from repro.graphs.triangle_counting import degree_relabel
        with TraceAnnotation("relabel"):
            L = tril(degree_relabel(self.adj), strict=True)
        with TraceAnnotation("masked_spgemm"):
            res = masked_spgemm(L, L, L, **self.options)
        with TraceAnnotation("count"):
            rows = np.repeat(np.arange(L.nrows, dtype=np.int32),
                             np.diff(L.indptr))
            slots = (np.arange(L.nnz) - L.indptr[rows]).astype(np.int32)
            values, present, cols = jax.device_get(self._per_edge(
                res.vals, res.present, res.mask_cols, rows, slots))
            count = float(values[present].sum(dtype=np.float64))
        return Output(L.indptr, L.indices, values, present, cols, count)


def compare(outputs: List[Output], ref: reference.Answer) -> dict:
    """Each number compared, worst over the solves, and the solves that
    failed.  ``count_gap``: distance of a solve's count from the
    reference's.  ``edge_gap``: edges of ``L`` whose value, presence or
    column differs from the reference, all of them where the program's
    ``L`` is not the reference's."""
    worst = dict.fromkeys(LIMITS, 0.0)
    failed = 0
    for out in outputs:
        gaps = {"count_gap": abs(out.count - ref.count)}
        if (np.array_equal(out.indptr, ref.indptr)
                and np.array_equal(out.indices, ref.indices)):
            wrong = ((out.values != ref.support)
                     | (out.present != (ref.support > 0))
                     | (out.cols != ref.indices))
            gaps["edge_gap"] = float(np.count_nonzero(wrong))
        else:
            gaps["edge_gap"] = float(max(len(ref.indices),
                                         len(out.indices)))
        failed += any(gaps[k] > LIMITS[k] for k in LIMITS)
        for k in LIMITS:
            worst[k] = max(worst[k], gaps[k])
    return {"worst": worst, "failed": failed}


class CompileCounter:
    """Counts programs compiled or loaded from the cache while open."""

    def __init__(self):
        self.count = 0

    def _listen(self, event: str, duration_secs: float, **_):
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


def run_window(solve: Callable[[], Output], seconds: float):
    """Solves back to back until ``seconds`` have passed; returns the
    outputs and the window's length, which ends with the last solve."""
    outputs = []
    t0 = time.perf_counter()
    while True:
        outputs.append(solve())
        if time.perf_counter() - t0 >= seconds:
            break
    return outputs, time.perf_counter() - t0


@dataclasses.dataclass
class Readings:
    """What a traced run hands the per-layer metric readers."""

    solves: int                 # solves in the traced window
    spans: List[dict]           # repro.obs spans of the traced window
    setup_spans: List[dict]     # repro.obs spans of set-up
    compiles: int               # programs compiled or loaded in the window
    trace: Optional[dict]       # the window's profiler trace, plain form
    window: Optional[tuple]     # the window on the trace's clock, in ns
    work: Optional[dict]        # flops and bytes one solve requires
    peaks: Optional[dict]       # the device's row of peaks.json
    bench_dir: object = spec.BENCH_DIR

    def planes(self) -> List[dict]:
        if self.trace is None or self.window is None:
            return []
        return xtrace.device_planes(self.trace)

    def device_seconds(self, line: str, pattern: str) -> Optional[float]:
        """Device seconds per solve of the events on ``line`` whose name
        matches ``pattern``, averaged over the chips; None where the
        trace holds none."""
        planes = self.planes()
        events = [xtrace.events_matching(p, line, pattern) for p in planes]
        if not any(events):
            return None
        total = sum(xtrace.seconds(ev, self.window) for ev in events)
        return total / len(planes) / self.solves

    def read(self, name: str) -> Optional[float]:
        """Another metric's value, from its own reader."""
        return spec.metric_reader(self.bench_dir, name)(self)


def _trace_window(solve, seconds):
    """The window run under the profiler and ``repro.obs`` spans."""
    import jax
    from repro import obs
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with obs.tracing(capacity=1 << 16) as tr:
                with jax.profiler.TraceAnnotation(xtrace.WINDOW):
                    outputs, window_s = run_window(solve, seconds)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        plain = xtrace.from_xplane(files[0]) if files else None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return outputs, window_s, tr.sink.spans(), plain


def _diagnose(solve: Solver, out: Output, traced: bool) -> None:
    """Earlier lines: the route the program takes on this input, and in
    a traced run the tile route's worklist (a few seconds of host work,
    kept out of the other runs)."""
    from repro.core import planner
    from repro.core.formats import CSR
    opts = solve.options
    L = CSR(out.indptr, out.indices, np.ones(len(out.indices), np.float32),
            solve.adj.shape)
    log(f"[graph] n={L.nrows} nnz(L)={L.nnz} widest L row="
        f"{int(np.diff(L.indptr).max())}")
    if opts.get("algorithm", "auto") == "auto":
        p = planner.plan(L, L, L)
        info = planner.explain_cached(p)
        log(f"[plan] elected {p.algorithm}, trialed {list(p.trialed)}, "
            f"widths {list(p.widths)}, modeled costs (ms) "
            f"{info['costs_ms']}")
    if traced and opts.get("algorithm") == "tile":
        from repro.core.formats import bcsr_from_csr
        from repro.kernels.masked_matmul import ops
        bs = opts["tile_block"]
        Lb = bcsr_from_csr(L, bs)
        sched = ops.build_spgemm_schedule(Lb, Lb, Lb)
        entries = len(sched[0])
        real = int(np.count_nonzero((sched[3] >> 1) & 1))
        chunks = len(ops.chunk_schedule(sched, ops.SPGEMM_CHUNK))
        log(f"[tile] bs={bs} nnzb={Lb.nnzb} worklist entries={entries} "
            f"(products {real}) chunks={chunks} per replay, two replays "
            f"(values, structure); each grid step reads "
            f"{2 * bs * bs * 4} bytes of blocks")


def execute(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            device: dict, t_start: float) -> dict:
    """One run of ``cell`` on ``device``; returns the result line."""
    import jax
    from repro import obs

    peaks = spec.load_peaks(cell.bench_dir).get(device["kind"])

    base = graphs.generate(cell.config["graph"])
    indptr, indices = graphs.permute(*base, seed)
    solve = Solver(indptr, indices, cell.traffic["masked_spgemm"])
    with obs.tracing() as setup_tr:
        solve()
    setup_spans = setup_tr.sink.spans()

    with CompileCounter() as compiles:
        setup_s = time.perf_counter() - t_start
        if traced:
            outputs, window_s, spans, plain = _trace_window(solve, seconds)
        else:
            outputs, window_s = run_window(solve, seconds)
            spans, plain = [], None
    stats = [d.memory_stats() or {} for d in jax.devices()[:cell.chips]]
    log(f"[memory] {stats}")
    peak = [s.get("peak_bytes_in_use") for s in stats]
    if all(p is not None for p in peak):
        device = dict(device, memory_peak_bytes=max(peak))
    log(f"[window] {len(outputs)} solves in {window_s:.3f}s, set-up "
        f"{setup_s:.3f}s, {compiles.count} compiles in the window")
    _diagnose(solve, outputs[0], traced)

    ref = reference.triangles(indptr, indices)
    checked = compare(outputs, ref)
    log(f"[reference] triangles={ref.count} nnz(L)={len(ref.indices)}; "
        f"solve counts {sorted({o.count for o in outputs})}")

    metrics: Dict[str, dict] = {}
    result = {"correct": checked["failed"] == 0 and len(outputs) > 0,
              "attempted": len(outputs), "failed": checked["failed"]}
    if not traced:
        values = {"setup_s": setup_s, "solve_s": window_s / len(outputs),
                  "peak_hbm_gb": (device.get("memory_peak_bytes", 0) / 1e9
                                  or None)}
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        win = xtrace.window(plain) if plain else None
        lo_ptr = ref.indptr
        triple = (lo_ptr, ref.indices, (len(lo_ptr) - 1,) * 2)
        need = work.required(
            len(lo_ptr) - 1, len(ref.indices), len(ref.indices),
            len(ref.indices), work.products_in_mask(triple, triple, triple))
        readings = Readings(
            solves=len(outputs), spans=spans, setup_spans=setup_spans,
            compiles=compiles.count, trace=plain, window=win, work=need,
            peaks=peaks, bench_dir=cell.bench_dir)
        if peaks:
            t, bound = work.roofline_seconds(need, peaks)
            log(f"[work] per solve {need['flops']} flops, {need['bytes']} "
                f"bytes: at least {t:.3e}s on this chip, {bound}-bound")
        for m in cell.per_layer:
            v = spec.metric_reader(cell.bench_dir, m["name"])(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        planes = readings.planes()
        if planes:
            busy = [sum(e - s for s, e in xtrace.busy(p, win))
                    for p in planes]
            device = dict(device, busy_s=sum(busy) / len(busy) / 1e9,
                          window_s=(win[1] - win[0]) / 1e9)
            annotations = [e for e in xtrace.host_events(plain)
                           if e[0] in PHASES]
            result["breakdown"] = {
                "device_ops": xtrace.top_ops(planes[0], win),
                "idle_gaps": xtrace.top_gaps(planes[0], win, annotations)}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": checked["worst"][k], "limit": LIMITS[k]}
                        for k in LIMITS}
    return result


def main(argv: List[str], t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="One run of one benchmark cell (see BENCHMARK.json).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        device = check_device(cell.chips, spec.load_peaks(cell.bench_dir))
    except (spec.SpecError, DeviceError, OSError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax
    from repro import compile_cache
    log(f"[device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    log(f"[cache] compile cache at {compile_cache.enable()}")
    # cache every program, however fast it compiles, so that only a
    # cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     device, t_start)
    for k, c in result["checks"].items():
        print(f"{k} {c['value']} limit {c['limit']}", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0
