"""Observability layer (PR 9): spans, sinks, explain, exposition, HTTP.

The contracts under test:

* span sites cost one branch when tracing is off, and spans NEVER feed
  scheduling — a traced engine produces the same
  ``deterministic_snapshot()`` as an untraced one;
* span/trace ids are deterministic counters (replay-stable), nesting
  links parents, and the Chrome-trace export round-trips;
* ``planner.explain`` decomposes every plan into its cost-feature
  vector + per-candidate modeled costs (the repro.tune residual feed);
* the Prometheus exposition round-trips through its own parser and the
  stdlib HTTP endpoint serves it live.
"""
import json
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.core.formats import CSR, erdos_renyi, er_mask
from repro.core.planner import explain, plan
from repro.obs.exposition import (HISTOGRAM_BUCKETS, parse_prometheus,
                                  render_prometheus)
from repro.obs.sinks import InMemorySink, JsonlSpanSink, load_spans
from repro.obs.spans import _NULL_SPAN
from repro.serving import QueryEngine


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends untraced (the process default)."""
    obs.disable()
    yield
    obs.disable()


def _operands(n=64, seed=0):
    return (erdos_renyi(n, 3, seed=seed), erdos_renyi(n, 3, seed=seed + 1),
            er_mask(n, 6, seed=seed + 2))


def _revalue(x: CSR, seed: int) -> CSR:
    rng = np.random.default_rng(seed)
    return CSR(x.indptr, x.indices,
               rng.uniform(0.5, 1.5, x.nnz).astype(np.float32), x.shape)


# ---------------------------------------------------------------------------
# spans: disabled cost, nesting, determinism
# ---------------------------------------------------------------------------


def test_disabled_sites_are_null_and_shared():
    assert not obs.enabled()
    s = obs.span("anything", attr=1)
    assert s is _NULL_SPAN and s is obs.span("other")
    with s as inner:
        inner.set(whatever=2)           # all no-ops
    assert obs.event("x") is None
    assert obs.new_trace() is None
    assert obs.current_spans() == []


def test_span_nesting_links_parents_and_traces():
    with obs.tracing() as tr:
        tid = obs.new_trace()
        with obs.span("outer", trace=tid) as outer:
            with obs.span("inner") as inner:
                obs.event("leaf", dur_s=0.5)
    recs = {r["name"]: r for r in tr.sink.spans()}
    # exit order: inner closes first
    assert [r["name"] for r in tr.sink.spans()] == ["leaf", "inner",
                                                    "outer"]
    assert recs["outer"]["parent"] is None
    assert recs["inner"]["parent"] == outer.span_id
    assert recs["leaf"]["parent"] == inner.span_id
    # the trace id set on the outer span flows to everything nested
    assert {recs[k]["trace"] for k in recs} == {tid}
    assert recs["leaf"]["dur"] == 0.5


def test_span_ids_are_deterministic_counters():
    def capture():
        with obs.tracing() as tr:
            t1, t2 = obs.new_trace(), obs.new_trace()
            with obs.span("a", trace=t1):
                pass
            with obs.span("b", trace=t2):
                pass
        return [(r["span"], r["trace"]) for r in tr.sink.spans()]

    assert capture() == capture() == [(1, 1), (2, 2)]


def test_span_records_error_and_attrs():
    with obs.tracing() as tr:
        with pytest.raises(RuntimeError):
            with obs.span("boom", stage="setup") as sp:
                sp.set(progress=3)
                raise RuntimeError("x")
    (rec,) = tr.sink.spans()
    assert rec["error"] == "RuntimeError"
    assert rec["attrs"] == {"stage": "setup", "progress": 3}
    assert rec["dur"] >= 0.0


def test_tracing_scope_restores_previous_tracer():
    t_outer = obs.configure()
    with obs.tracing() as t_inner:
        assert obs.get_tracer() is t_inner is not t_outer
    assert obs.get_tracer() is t_outer
    obs.disable()
    assert not obs.enabled()


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------


def test_inmemory_sink_is_a_bounded_ring():
    sink = InMemorySink(capacity=3)
    with obs.tracing(sink):
        for i in range(5):
            obs.event(f"e{i}")
    assert len(sink) == 3 and sink.emitted == 5
    assert [r["name"] for r in sink.spans()] == ["e2", "e3", "e4"]
    sink.clear()
    assert len(sink) == 0 and sink.emitted == 5


def test_jsonl_sink_roundtrips_and_rotates(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    with JsonlSpanSink(path, max_bytes=512, rotate=16) as sink:
        with obs.tracing(sink):
            for i in range(24):
                obs.event("serve.exec", dur_s=i * 1e-3, idx=i)
    assert sink.written == 24
    assert len(sink.segments()) >= 2                # rotation happened
    # header lines carry the span kind, so loaders skip them
    head = json.loads(open(path).readline())
    assert head["kind"] == "repro-span-trace"
    recs = load_spans(path, rotate=16)
    assert len(recs) == 24                          # headers not counted
    assert [r["attrs"]["idx"] for r in recs] == list(range(24))


def test_jsonl_sink_seeded_sampling(tmp_path):
    def run(fname, seed):
        s = JsonlSpanSink(str(tmp_path / fname), sample_rate=0.5,
                          seed=seed)
        with obs.tracing(s):
            for i in range(40):
                obs.event("e", idx=i)
        s.close()
        return [r["attrs"]["idx"]
                for r in load_spans(str(tmp_path / fname))]

    a, b = run("a.jsonl", seed=5), run("b.jsonl", seed=5)
    assert a == b and 0 < len(a) < 40
    assert run("c.jsonl", seed=6) != a


# ---------------------------------------------------------------------------
# export: Chrome trace events + modeled-vs-measured residuals
# ---------------------------------------------------------------------------


def test_chrome_trace_export_shape():
    with obs.tracing() as tr:
        with obs.span("serve.exec", algorithm="msa"):
            obs.event("spgemm.row", dur_s=1e-3)
    doc = obs.chrome_trace(tr.sink.spans())
    evs = doc["traceEvents"]
    assert len(evs) == 2 and all(e["ph"] == "X" for e in evs)
    by_name = {e["name"]: e for e in evs}
    assert by_name["spgemm.row"]["dur"] == pytest.approx(1e3)  # micros
    assert by_name["serve.exec"]["args"]["algorithm"] == "msa"
    assert by_name["serve.exec"]["cat"] == "serve"
    assert min(e["ts"] for e in evs) == 0.0         # rebased to t_min
    json.dumps(doc)                                 # serializable as-is


def test_save_chrome_trace_writes_loadable_json(tmp_path):
    with obs.tracing() as tr:
        obs.event("x", dur_s=0.25)
    p = tmp_path / "trace.json"
    obs.save_chrome_trace(str(p), tr.sink.spans())
    loaded = json.load(open(p))
    assert len(loaded["traceEvents"]) == 1
    assert loaded["displayTimeUnit"] == "ms"


def test_residuals_pair_modeled_and_measured():
    with obs.tracing() as tr:
        obs.event("serve.exec", dur_s=2e-3, algorithm="msa", route="row",
                  modeled_ms=1.0)
        obs.event("serve.exec", dur_s=4e-3, algorithm="msa", route="row",
                  modeled_ms=1.0)
        obs.event("serve.exec", dur_s=1e-3, route="burst")  # no model
    rows = obs.residuals(tr.sink.spans())
    assert len(rows) == 2
    assert rows[0]["residual"] == pytest.approx(2.0)
    summary = obs.export.residual_summary(tr.sink.spans())
    assert summary["msa"]["count"] == 2
    assert summary["msa"]["mean_residual"] == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# planner.explain
# ---------------------------------------------------------------------------


def test_explain_decomposes_row_plan():
    A, B, M = _operands()
    info = explain(plan(A, B, M))
    assert info["elected"] == info["algorithm"]
    assert info["elected"] in info["costs_ms"]
    assert info["elected_cost_ms"] == min(info["costs_ms"].values())
    # every candidate cost decomposes into its feature vector
    for algo, feats in info["features"].items():
        assert algo in info["costs_ms"]
        assert all(np.isfinite(v) for v in feats.values())
    assert info["stats"]["n"] == 64
    assert isinstance(info["cost_model_token"], str)
    json.dumps(info)                                # span-attachable


def test_explain_decomposes_dist_plan():
    from repro.core.planner import plan_distributed
    A, B, M = _operands(n=96)
    info = explain(plan_distributed(A, B, M, 2))
    assert info["route"] in ("row", "ring")
    assert info["p"] == 2
    assert set(info["costs_ms"]) >= {"row", "ring"}
    json.dumps(info)


def test_plan_build_span_carries_explain():
    from repro.core.planner import clear_plan_cache
    clear_plan_cache()
    A, B, M = _operands(seed=11)
    with obs.tracing() as tr:
        p = plan(A, B, M)
        plan(A, B, M)                       # cache hit: no second span
    builds = [r for r in tr.sink.spans() if r["name"] == "plan.build"]
    assert len(builds) == 1
    ex = builds[0]["attrs"]["explain"]
    assert ex["elected"] == p.algorithm
    assert builds[0]["attrs"]["algorithm"] == p.algorithm


# ---------------------------------------------------------------------------
# exposition + HTTP endpoint
# ---------------------------------------------------------------------------


def test_render_parse_roundtrip_with_histograms():
    with obs.tracing():
        obs.event("serve.exec", dur_s=5e-4)
        obs.event("serve.exec", dur_s=2e-2)
        text = render_prometheus()
    samples = parse_prometheus(text)
    name = "repro_span_duration_seconds"
    count = samples[(f"{name}_count", (("phase", "serve.exec"),))]
    total = samples[(f"{name}_sum", (("phase", "serve.exec"),))]
    inf = samples[(f"{name}_bucket",
                   (("le", "+Inf"), ("phase", "serve.exec")))]
    assert count == inf == 2.0
    assert total == pytest.approx(5e-4 + 2e-2)
    # buckets are cumulative (monotone in le)
    counts = [samples[(f"{name}_bucket",
                       (("le", repr(le)), ("phase", "serve.exec")))]
              for le in HISTOGRAM_BUCKETS]
    assert counts == sorted(counts) and counts[-1] == 2.0
    # registry caches appear with labels
    assert any(k[0] == "repro_cache_size" for k in samples)


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError):
        parse_prometheus("not a sample line at all with {\n")
    with pytest.raises(ValueError):
        parse_prometheus('metric{label=unquoted} 1\n')


def test_http_endpoint_serves_metrics_and_health():
    A, B, M = _operands()
    with QueryEngine(expose_port=0) as engine:
        engine.serve([(A, B, M)])
        engine.serve([(A, B, M)])                   # result-cache hit
        base = engine.obs_server.url
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            samples = parse_prometheus(r.read().decode())
        with urllib.request.urlopen(f"{base}/health", timeout=10) as r:
            health = json.loads(r.read().decode())
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=10)
    assert samples[("repro_serve_completed_total", ())] == 2.0
    assert samples[("repro_serve_result_cache_hits_total", ())] == 1.0
    assert ("repro_serve_queue_depth", ()) in samples
    assert health["status"] == "ok" and health["queue_depth"] == 0
    assert health["completed"] == 2 and health["stopped"] is False


def test_engine_close_shuts_exposition_down():
    engine = QueryEngine(expose_port=0)
    url = engine.obs_server.url
    engine.close()
    assert engine.obs_server is None
    with pytest.raises(Exception):
        urllib.request.urlopen(f"{url}/health", timeout=2)


# ---------------------------------------------------------------------------
# engine integration: lifecycle spans + determinism
# ---------------------------------------------------------------------------


def test_request_lifecycle_spans_cover_the_pipeline():
    from repro.core.planner import clear_plan_cache
    clear_plan_cache()
    A, B, M = _operands(seed=21)
    stream = [(_revalue(A, s), B, M) for s in range(4)]
    with obs.tracing() as tr:
        with QueryEngine(cache_results=True) as engine:
            engine.serve(stream)
            engine.serve([stream[0]])               # exact repeat -> hit
    names = {r["name"] for r in tr.sink.spans()}
    assert {"serve.submit", "serve.queue_wait", "serve.plan",
            "serve.exec", "serve.result_cache_put",
            "serve.cache_hit"} <= names
    # per-request trace ids: every submit got its own
    submits = [r for r in tr.sink.spans() if r["name"] == "serve.submit"]
    assert len(submits) == 5
    tids = [r["trace"] for r in submits]
    assert len(set(tids)) == 5 and None not in tids
    # the exec event links back to the bucket's member traces
    execs = [r for r in tr.sink.spans() if r["name"] == "serve.exec"]
    assert execs and set(execs[0]["attrs"]["traces"]) <= set(tids)


def test_delta_lifecycle_spans():
    from repro.core.formats import CSRDelta
    A, B, M = _operands(seed=31)
    with obs.tracing() as tr:
        with QueryEngine(max_batch=8) as engine:
            engine.serve([(A, B, M)])
            delta = CSRDelta.upserts([0, 2], [3, 5], [1.5, 0.25])
            engine.submit_delta(A, B, M, delta_a=delta)
    names = {r["name"] for r in tr.sink.spans()}
    assert {"delta.apply", "delta.revalidate",
            "delta.invalidate"} <= names
    recs = {r["name"]: r for r in tr.sink.spans()}
    assert recs["delta.apply"]["attrs"]["applied"] == 1  # one operand delta
    assert "survived" in recs["delta.revalidate"]["attrs"]


def test_tracing_never_perturbs_deterministic_snapshot():
    A, B, M = _operands(seed=41)
    stream = [(_revalue(A, s), B, M) for s in range(6)]

    def run(traced):
        with QueryEngine(cache_results=False) as engine:
            if traced:
                with obs.tracing():
                    engine.serve(stream)
            else:
                engine.serve(stream)
            return engine.metrics.deterministic_snapshot()

    assert run(traced=False) == run(traced=True)


# ---------------------------------------------------------------------------
# ServeMetrics: hit/miss latency split (the percentile-skew fix)
# ---------------------------------------------------------------------------


def test_cache_hit_latencies_tracked_separately():
    from repro.serving.metrics import ServeMetrics
    m = ServeMetrics()
    m.record_bucket(size=3, algorithm="msa", route="row",
                    queue_wait_s=0.0, plan_s=0.0, exec_s=0.3,
                    latencies_s=(0.10, 0.20, 0.30))
    for s in (0.001, 0.002):
        m.record_cache_hit(latency_s=s)
    snap = m.snapshot()
    assert snap["miss_lat_count"] == 3 and snap["hit_lat_count"] == 2
    assert snap["lat_count"] == 5                   # combined view
    # hits no longer silently vanish: combined p50 sits below miss-only
    assert snap["lat_p50_s"] < snap["miss_lat_p50_s"]
    assert snap["hit_lat_p99_s"] < snap["miss_lat_p50_s"]
    # legacy no-latency call still counts the hit, skews nothing
    m.record_cache_hit()
    snap2 = m.snapshot()
    assert snap2["result_cache_hits"] == 3
    assert snap2["hit_lat_count"] == 2


def test_engine_records_hit_latency():
    A, B, M = _operands(seed=51)
    with QueryEngine() as engine:
        engine.serve([(A, B, M)])
        engine.serve([(A, B, M)])
        snap = engine.metrics.snapshot()
    assert snap["result_cache_hits"] == 1
    assert snap["hit_lat_count"] == 1
    assert snap["lat_count"] == snap["miss_lat_count"] + 1


# ---------------------------------------------------------------------------
# wiring
# ---------------------------------------------------------------------------


def test_obs_registered_in_benchmark_order():
    from benchmarks.run import ORDER
    assert "obs" in ORDER


def test_bench_save_attaches_cache_info(tmp_path, monkeypatch):
    from benchmarks.common import save
    monkeypatch.setenv("REPRO_BENCH_OUT", str(tmp_path))
    path = save("unit_grid", {"k": 1})
    payload = json.load(open(path))
    assert payload["k"] == 1
    info = payload["_cache_info"]
    assert "planner-plans" in info
    assert {"size", "capacity", "hits", "misses"} <= set(
        next(iter(info.values())))


# ---------------------------------------------------------------------------
# counter tracks (PR 10): Perfetto "C" events alongside the slices
# ---------------------------------------------------------------------------


def test_counter_tracks_export_as_chrome_counters():
    with obs.tracing() as tr:
        obs.counter("serve.queue_depth", 3)
        with obs.span("serve.exec"):
            obs.counter("serve.inflight", 2.5)
        spans = tr.sink.spans()
    evs = obs.chrome_trace(spans)["traceEvents"]
    counters = [e for e in evs if e["ph"] == "C"]
    slices = [e for e in evs if e["ph"] == "X"]
    assert len(counters) == 2 and len(slices) == 1
    by_name = {e["name"]: e for e in counters}
    assert by_name["serve.queue_depth"]["args"] == {"value": 3.0}
    assert by_name["serve.inflight"]["args"] == {"value": 2.5}
    # counter records carry no duration and sit on the emitting thread's
    # row like any slice
    assert all("dur" not in e and e["pid"] == 1 for e in counters)
    assert all(e["cat"] == "serve" for e in counters)


def test_counter_disabled_is_a_noop():
    assert not obs.enabled()
    obs.counter("serve.queue_depth", 9)     # must not raise or record
    with obs.tracing() as tr:
        pass
    assert tr.sink.spans() == []


def test_counter_records_skipped_by_span_histograms():
    with obs.tracing() as tr:
        obs.counter("serve.queue_depth", 4)
        obs.event("serve.exec", dur_s=0.01)
        text = render_prometheus(tracer=tr)
    samples = parse_prometheus(text)
    # the exec span histogram exists; no histogram family for the counter
    assert ("repro_span_duration_seconds_count",
            (("phase", "serve.exec"),)) in samples
    assert not any("queue_depth" in name for name, _ in samples)


# ---------------------------------------------------------------------------
# exposition parser edge cases (PR 10): the round trip is lossless
# ---------------------------------------------------------------------------


def test_parse_prometheus_nonfinite_values():
    import math
    text = ('b_bucket{le="+Inf"} 7\n'
            'q{quantile="0.99"} NaN\n'
            'lo -Inf\n'
            'hi +Inf\n')
    s = parse_prometheus(text)
    assert s[("b_bucket", (("le", "+Inf"),))] == 7.0
    assert math.isnan(s[("q", (("quantile", "0.99"),))])
    assert s[("lo", ())] == float("-inf")
    assert s[("hi", ())] == float("inf")


def test_parse_prometheus_unescapes_label_values():
    text = ('m{v="a\\nb\\"c\\\\d"} 1\n'
            'm{v="x,y"} 2\n'          # comma inside quotes
            'm{v="tail\\\\"} 3\n')    # value ENDING in a backslash
    s = parse_prometheus(text)
    assert s[("m", (("v", 'a\nb"c\\d'),))] == 1.0
    assert s[("m", (("v", "x,y"),))] == 2.0
    assert s[("m", (("v", "tail\\"),))] == 3.0


def test_render_parse_round_trip_is_lossless():
    import math
    from repro.obs.exposition import _Writer
    w = _Writer()
    w.sample("rt_nan", float("nan"))
    w.sample("rt_inf", float("inf"))
    w.sample("rt_esc", 1.5, {"path": 'a\\b"c\nd', "tail": "z\\"})
    s = parse_prometheus(w.render())
    assert math.isnan(s[("rt_nan", ())])
    assert s[("rt_inf", ())] == float("inf")
    assert s[("rt_esc", (("path", 'a\\b"c\nd'), ("tail", "z\\")))] == 1.5


# ---------------------------------------------------------------------------
# residual extraction robustness (PR 10): sparse/empty captures
# ---------------------------------------------------------------------------


def test_residuals_tolerate_empty_and_planless_captures():
    from repro.obs.export import residual_summary, residuals
    assert residuals([]) == []
    assert residuals(None) == []
    assert residual_summary([]) == {}
    assert residual_summary(None) == {}
    # spans exist but none carries a modeled cost (plan spans absent)
    planless = [{"name": "serve.exec", "dur": 0.01},
                {"name": "serve.queue_wait", "dur": 0.0},
                {"name": "serve.exec", "counter": 1.0}]
    assert residuals(planless) == []
    assert residual_summary(planless) == {}


def test_residual_record_filters_and_normalizes():
    from repro.obs.export import residual_record
    rec = {"name": "serve.exec", "dur": 4e-3,
           "attrs": {"modeled_ms": 2.0, "size": 2, "algorithm": "msa",
                     "route": "batched", "regime": "r"}}
    r = residual_record(rec)
    assert r["residual"] == pytest.approx(1.0)      # 4ms / (2ms * 2)
    assert r["size"] == 2 and r["algorithm"] == "msa"
    assert residual_record({"name": "other", "dur": 1.0}) is None
    assert residual_record({"name": "serve.exec", "counter": 2.0}) is None
    bad = {"name": "serve.exec", "dur": 1.0,
           "attrs": {"modeled_ms": "garbage"}}
    assert residual_record(bad) is None
    zero = {"name": "serve.exec", "dur": 1.0, "attrs": {"modeled_ms": 0.0}}
    assert residual_record(zero) is None


# ---------------------------------------------------------------------------
# host-prep spans and the profiler's clock
# ---------------------------------------------------------------------------


def _lower(n=96, seed=5):
    from repro.core.formats import tril
    return tril(erdos_renyi(n, 8, seed=seed, values="ones"))


def _tile_solve(L, backend="pallas"):
    from repro.core.masked_spgemm import _masked_spgemm_tile
    return _masked_spgemm_tile(L, L, L, block_size=8, backend=backend,
                               interpret=True if backend == "pallas"
                               else None)


def test_tile_route_spans_name_its_host_work():
    """One schedule build, one chunking for the one walk that carries
    values and structure (two planes), three BCSR conversions (A and B
    with their pattern plane, the mask); host_prep still holds exactly the
    BCSR conversions, and the rest sits beside it."""
    from repro.core.formats import bcsr_from_csr
    from repro.kernels.masked_matmul import ops
    L = _lower()
    with obs.tracing() as tr:
        _tile_solve(L)
    recs = tr.sink.spans()
    names = [r["name"] for r in recs]
    assert (names.count("spgemm.schedule"), names.count("spgemm.chunk"),
            names.count("spgemm.bcsr"), names.count("spgemm.gather")) \
        == (1, 1, 3, 1)

    def children(name):
        (parent,) = [r for r in recs if r["name"] == name]
        return sorted(r["name"] for r in recs
                      if r["parent"] == parent["span"])

    assert children("spgemm.host_prep") == ["spgemm.bcsr"] * 3
    assert children("spgemm.tile") == sorted(
        ["spgemm.host_prep", "spgemm.schedule", "spgemm.chunk",
         "spgemm.h2d", "spgemm.gather"])
    Lb = bcsr_from_csr(L, 8)
    flags = ops.build_spgemm_schedule(Lb, Lb, Lb)[3]
    entries = len(flags)
    zero_fill = int(np.count_nonzero((flags & 2) == 0))
    attrs = {r["name"]: r.get("attrs") for r in recs}
    sched = attrs["spgemm.schedule"]
    assert set(sched) == {"entries", "candidates", "lookup"}
    assert sched["entries"] == entries
    assert sched["candidates"] >= entries - zero_fill
    assert sched["lookup"] in ("table", "search")
    assert attrs["spgemm.bcsr"] == {"bs": 8, "nnzb": Lb.nnzb}
    assert attrs["spgemm.chunk"] == {"chunks": 1, "planes": 2,
                                     "steps": entries}


def _h2d_case(route, L):
    """A solve on ``route``, and the bytes its device operands take,
    computed from their shapes (f32 values, int32 ids and lengths)."""
    from repro.core.formats import bcsr_from_csr
    from repro.core.masked_spgemm import (masked_spgemm,
                                          masked_spgemm_batched)
    from repro.kernels.masked_matmul import ops
    m = L.nrows
    widest = int(np.diff(L.indptr).max())
    widest_col = int(np.bincount(L.indices, minlength=m).max())
    padded = lambda w: m * w * 8 + m * 4  # noqa: E731  cols, vals, lens
    if route == "tile":
        Lb = bcsr_from_csr(L, 8)
        chunk_len = min(ops.SPGEMM_CHUNK,
                        len(ops.build_spgemm_schedule(Lb, Lb, Lb)[0]))
        return (lambda: _tile_solve(L),
                5 * Lb.nnzb * 8 * 8 * 4        # three operands, two patterns
                + 4 * chunk_len * 4            # one chunk, walked once
                + padded(widest)               # the mask, padded
                + 5 * L.nnz * 4)               # gather addressing
    if route == "ring":
        from repro.core.distributed import (clear_ring_prep_cache,
                                            device_mesh,
                                            ring_sparse_masked_spgemm)
        Lb = bcsr_from_csr(L, 8)
        flags = ops.build_spgemm_schedule(Lb, Lb, Lb)[3]
        products = int(np.count_nonzero((flags >> 1) & 1))

        def solve():
            clear_ring_prep_cache()     # a structure's first call
            ring_sparse_masked_spgemm(L, L, L, device_mesh(1), block_size=8)
        return (solve,
                2 * L.nnz * 4                  # A's and B's values
                + 2 * 3 * L.nnz * 4            # their block coordinates
                + 4 * min(ops.SPGEMM_CHUNK, products) * 4   # one chunk
                + 5 * L.nnz * 4                # extraction addressing
                + m * 4                        # rows back in mask order
                + padded(widest))              # the mask, padded
    if route == "row":
        return (lambda: masked_spgemm(L, L, L, algorithm="inner"),
                2 * padded(widest) + padded(widest_col))    # A, M; B^T
    return (lambda: masked_spgemm_batched([L, L], L, [L, L],
                                          algorithm="msa"),
            2 * (2 * padded(widest)) + padded(widest))      # A, M; B


@pytest.mark.parametrize("route", ["tile", "row", "batched", "ring"])
def test_h2d_bytes_are_the_device_operands(route):
    solve, want = _h2d_case(route, _lower())
    with obs.tracing() as tr:
        solve()
    h2d = [r for r in tr.sink.spans() if r["name"] == "spgemm.h2d"]
    assert h2d and sum(r["attrs"]["bytes"] for r in h2d) == want


@pytest.mark.parametrize("name", ["graph.relabel", "graph.tril"])
def test_graph_prep_spans(name):
    from repro.core.formats import tril
    from repro.graphs.triangle_counting import degree_relabel
    adj = erdos_renyi(64, 4, seed=2, values="ones")
    with obs.tracing() as tr:
        tril(degree_relabel(adj))
    recs = tr.sink.spans()
    assert [r["name"] for r in recs].count(name) == 1
    (r,) = [r for r in recs if r["name"] == name]
    assert r["parent"] is None and r["dur"] > 0


def _host_plane_events(tmp_path, body):
    """Events of the profile's host plane while ``body`` runs under
    ``jax.profiler`` (python tracer off, as the benchmark traces)."""
    import glob
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]


def test_spans_sit_on_the_profilers_host_plane(tmp_path):
    """Every span record appears once on the profile's host plane, under
    its own name, lasting as long as the record says; events and counters
    are not mirrored."""
    L = _lower()
    recs = []

    def body():
        with obs.tracing() as tr:
            _tile_solve(L, backend="xla")
            obs.event("mirror.event", dur_s=0.5)
            obs.counter("mirror.counter", 1.0)
        recs.extend(tr.sink.spans())

    events = _host_plane_events(tmp_path, body)
    spans = [r for r in recs if r["name"].startswith("spgemm.")]
    assert {"spgemm.tile", "spgemm.schedule", "spgemm.h2d",
            "spgemm.gather"} <= {r["name"] for r in spans}
    for name in {r["name"] for r in spans}:
        mine = sorted((r["t0"], r["dur"]) for r in spans
                      if r["name"] == name)
        seen = sorted((s, d) for n, s, d in events if n == name)
        assert len(seen) == len(mine), name
        for (_, dur), (_, ns) in zip(mine, seen):
            assert ns / 1e9 == pytest.approx(dur, rel=0.05, abs=2e-3)
    names = {n for n, _, _ in events}
    assert "mirror.event" not in names and "mirror.counter" not in names


def test_tracing_off_mirrors_nothing(tmp_path):
    """Off, a span site still hands back the shared no-op span, and a solve
    under the profiler leaves none of the program's span names there."""
    L = _lower()
    assert obs.span("spgemm.h2d") is _NULL_SPAN
    events = _host_plane_events(tmp_path, lambda: _tile_solve(L, "xla"))
    names = {n for n, _, _ in events}
    assert not {n for n in names if n.startswith(("spgemm.", "graph."))}


def test_ring_prep_and_host_prep_spans():
    """The sparse ring's cold structure prep runs under one
    ``spgemm.ring_prep`` span (ring size, block size, blocks and products
    per device) and only on a structure's first call; the per-call value
    gather runs under ``spgemm.host_prep`` on every call."""
    from repro.core.distributed import (clear_ring_prep_cache, device_mesh,
                                        ring_sparse_masked_spgemm)
    from repro.core.formats import bcsr_from_csr
    from repro.kernels.masked_matmul import ops
    L = _lower()
    clear_ring_prep_cache()
    recs = []
    for _ in range(2):
        with obs.tracing() as tr:
            ring_sparse_masked_spgemm(L, L, L, device_mesh(1), block_size=8)
        recs.append(tr.sink.spans())
    names = [[r["name"] for r in rs] for rs in recs]
    assert names[0].count("spgemm.ring_prep") == 1
    assert "spgemm.ring_prep" not in names[1]
    for rs in recs:
        (prep,) = [r for r in rs if r["name"] == "spgemm.host_prep"]
        assert prep["attrs"] == {"algorithm": "ring"} and prep["dur"] > 0
    (ring,) = [r for r in recs[0] if r["name"] == "spgemm.ring_prep"]
    Lb = bcsr_from_csr(L, 8)
    flags = ops.build_spgemm_schedule(Lb, Lb, Lb)[3]
    products = int(np.count_nonzero((flags >> 1) & 1))
    assert ring["attrs"] == {"p": 1, "bs": 8, "blocks": [Lb.nnzb],
                             "slab_blocks": [Lb.nnzb],
                             "entries": [products],
                             "stage_entries": [products], "planes": 2,
                             "steps": products}
    # the schedule build is the prep's own, not a sibling of it
    (sched,) = [r for r in recs[0] if r["name"] == "spgemm.schedule"]
    assert sched["parent"] == ring["span"]
