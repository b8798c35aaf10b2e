"""Online health intelligence: windows, SLOs, drift.

The contracts under test:

* :class:`repro.obs.health.WindowAggregator` is a span sink with O(1)
  memory (ring-buffered shards, bounded samples) whose windows are
  driven entirely by the injectable clock — a virtual clock advances
  them deterministically, and data past the horizon expires;
* the SLO engine turns declarative objectives into multi-window burn
  rates: ``failing`` needs both windows hot, ``degraded`` only the
  long one, idle windows stay ``ok``;
* the drift detector folds normalized ``serve.exec`` residuals into
  per-(family, kernel, regime) Welford/EWMA stats, flags beyond the
  band with a concrete ``repro.tune --only`` recommendation, resets on
  a cost-model-token change, and skips burst-route spans;
* ``engine.health()`` + ``/health`` (503-with-reasons when failing) +
  ``/metrics`` ``repro_slo_*``/``repro_drift_*`` surface all of it.
"""
import json
import math
import subprocess
import sys
import urllib.error
import urllib.request

import pytest

from repro import caches, obs
from repro.core.formats import erdos_renyi, er_mask
from repro.core import planner
from repro.obs.drift import DriftDetector, family_of
from repro.obs.health import (HealthMonitor, HealthVerdict,
                              WindowAggregator, basic_verdict)
from repro.obs.slo import DEFAULT_SLOS, Objective, SLOEngine
from repro.serving import QueryEngine
from repro.serving.clock import VirtualClock


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    yield
    obs.disable()


def _operands(n=64, seed=0):
    return (erdos_renyi(n, 3, seed=seed), erdos_renyi(n, 3, seed=seed + 1),
            er_mask(n, 6, seed=seed + 2))


def _exec(dur=0.01, size=1, **attrs):
    return {"name": "serve.exec", "dur": dur,
            "attrs": {"size": size, **attrs}}


# ---------------------------------------------------------------------------
# WindowAggregator: ring shards on the injectable clock
# ---------------------------------------------------------------------------


def test_aggregator_windows_follow_virtual_clock():
    clk = VirtualClock()
    agg = WindowAggregator(clock=clk, horizon_s=60.0, shards=12)
    clk.advance(1.0)
    for _ in range(10):
        agg.emit({"name": "serve.error"})
        agg.emit(_exec())
    assert agg.window(60).count("serve.error") == 10
    assert agg.window(60).req_count("serve.exec") == 10
    assert agg.window(60).dur_sum("serve.exec") == pytest.approx(0.1)
    # advance past the short window but not the long one
    clk.advance(10.0)
    assert agg.window(5).count("serve.error") == 0
    assert agg.window(60).count("serve.error") == 10
    # advance past the horizon: everything expires (epoch check on read)
    clk.advance(120.0)
    assert agg.window(60).count("serve.error") == 0


def test_aggregator_ring_reuses_shards_in_place():
    clk = VirtualClock()
    agg = WindowAggregator(clock=clk, horizon_s=12.0, shards=4)
    for _ in range(50):            # many horizons worth of traffic
        agg.emit(_exec())
        clk.advance(3.0)           # one shard per emit
    assert len(agg._ring) == 4     # structure never grows
    # only the trailing horizon is visible
    assert agg.window(12).count("serve.exec") <= 4


def test_aggregator_bounds_percentile_samples():
    clk = VirtualClock()
    agg = WindowAggregator(clock=clk, horizon_s=60.0, shards=12,
                           sample_cap=4)
    for i in range(10):
        agg.emit(_exec(dur=i * 0.01))
    w = agg.window(60)
    assert w.count("serve.exec") == 10          # counts are exact
    assert len(w.samples("serve.exec")) == 4    # samples are bounded
    assert w.percentile("serve.exec", 0.99) <= 0.03


def test_aggregator_gauges_latest_wins():
    clk = VirtualClock()
    agg = WindowAggregator(clock=clk, horizon_s=60.0, shards=12)
    agg.emit({"name": "serve.queue_depth", "counter": 3.0})
    agg.emit({"name": "serve.queue_depth", "counter": 7.0})
    assert agg.window(60).gauge("serve.queue_depth") == 7.0
    clk.advance(6.0)                            # next shard
    agg.emit({"name": "serve.queue_depth", "counter": 1.0})
    assert agg.window(60).gauge("serve.queue_depth") == 1.0
    assert agg.window(60).gauge("missing") is None


def test_aggregator_validates_construction():
    with pytest.raises(ValueError):
        WindowAggregator(clock=VirtualClock(), horizon_s=0)
    with pytest.raises(ValueError):
        WindowAggregator(clock=VirtualClock(), shards=1)


# ---------------------------------------------------------------------------
# SLO engine: declarative objectives -> multi-window burn verdicts
# ---------------------------------------------------------------------------


def test_objective_derives_budgets_and_validates():
    assert Objective("p", "latency_p99", bound=0.25).budget == 0.01
    assert Objective("e", "error_rate", bound=0.02).budget == 0.02
    assert Objective("h", "cache_hit_rate", bound=0.9).budget \
        == pytest.approx(0.1)
    assert Objective("q", "queue_wait_share", bound=0.5).budget == 0.5
    with pytest.raises(ValueError, match="unknown SLO metric"):
        Objective("x", "nope", bound=1.0)
    with pytest.raises(ValueError, match="budget"):
        Objective("x", "error_rate", bound=0.01, budget=2.0)
    with pytest.raises(ValueError, match="short_s"):
        Objective("x", "error_rate", bound=0.01, short_s=90, long_s=60)
    with pytest.raises(ValueError, match="duplicate"):
        SLOEngine([Objective("a", "error_rate", bound=0.1)] * 2)


def _err_objective(**kw):
    kw.setdefault("min_events", 1)
    return Objective("err", "error_rate", bound=0.25, short_s=5.0,
                     long_s=60.0, **kw)


def test_slo_failing_needs_both_windows_degraded_only_long():
    clk = VirtualClock()
    agg = WindowAggregator(clock=clk, horizon_s=60.0, shards=12)
    eng = SLOEngine([_err_objective()])
    clk.advance(1.0)
    for _ in range(10):
        agg.emit({"name": "serve.error"})
        agg.emit(_exec())
    (st,) = eng.evaluate(agg)      # bad_frac 0.5 / budget 0.25 = 2.0x
    assert st.status == "failing" and "err" in st.reason
    assert st.burn_long == pytest.approx(2.0)
    # once the errors age out of the short window: degraded, not failing
    clk.advance(10.0)
    (st,) = eng.evaluate(agg)
    assert st.status == "degraded"
    assert st.burn_short == 0.0
    assert st.burn_long == pytest.approx(2.0)
    # and past the horizon: clean
    clk.advance(120.0)
    (st,) = eng.evaluate(agg)
    assert st.status == "ok" and st.reason == ""


def test_slo_idle_and_sparse_windows_stay_ok():
    clk = VirtualClock()
    agg = WindowAggregator(clock=clk, horizon_s=60.0, shards=12)
    eng = SLOEngine(DEFAULT_SLOS)
    assert all(st.status == "ok" for st in eng.evaluate(agg))
    # below min_events: even a 100% error rate must not flap the verdict
    agg.emit({"name": "serve.error"})
    assert all(st.status == "ok" for st in eng.evaluate(agg))


def test_slo_latency_p99_counts_over_bound_samples():
    clk = VirtualClock()
    agg = WindowAggregator(clock=clk, horizon_s=60.0, shards=12)
    obj = Objective("lat", "latency_p99", bound=0.1, budget=0.1,
                    min_events=1)
    eng = SLOEngine([obj])
    for _ in range(8):
        agg.emit(_exec(dur=0.01))
    (st,) = eng.evaluate(agg)
    assert st.status == "ok" and st.burn_long == 0.0
    for _ in range(8):
        agg.emit(_exec(dur=0.5))      # half the samples over the bound
    (st,) = eng.evaluate(agg)
    assert st.burn_long == pytest.approx(5.0)   # 0.5 / 0.1
    assert st.status == "failing"


def test_slo_queue_wait_share_and_hit_rate():
    clk = VirtualClock()
    agg = WindowAggregator(clock=clk, horizon_s=60.0, shards=12)
    for _ in range(4):
        agg.emit({"name": "serve.queue_wait", "dur": 0.9})
        agg.emit(_exec(dur=0.1))
        agg.emit({"name": "serve.submit", "dur": 0.0})
    qw = Objective("qw", "queue_wait_share", bound=0.4, min_events=1)
    (st,) = SLOEngine([qw]).evaluate(agg)
    assert st.burn_long == pytest.approx(0.9 / 0.4)  # share/budget
    assert st.status == "failing"
    hit = Objective("hits", "cache_hit_rate", bound=0.5, min_events=1)
    (st,) = SLOEngine([hit]).evaluate(agg)  # 0 hits of 4 submits
    assert st.burn_long == pytest.approx(2.0)        # miss 1.0 / budget 0.5
    assert st.status == "failing"


def test_health_verdict_worst_of_merges_reasons():
    a = HealthVerdict("ok")
    b = HealthVerdict("degraded", ("slow",))
    c = HealthVerdict("failing", ("down", "slow"))
    worst = HealthVerdict.worst(a, b, c)
    assert worst.status == "failing" and not worst.ok
    assert worst.reasons == ("slow", "down")        # deduped, ordered
    assert HealthVerdict.worst().status == "ok"
    assert b.as_dict() == {"status": "degraded", "reasons": ["slow"]}


# ---------------------------------------------------------------------------
# HealthMonitor: sink protocol, tee, verdict composition
# ---------------------------------------------------------------------------


def test_monitor_tees_to_inner_sink_and_exposes_spans():
    clk = VirtualClock()
    inner = obs.InMemorySink(capacity=64)
    mon = HealthMonitor(clock=clk, inner=inner, drift=None)
    with obs.tracing(mon):
        obs.event("serve.exec", dur_s=0.01, size=1)
        obs.counter("serve.queue_depth", 2)
    assert len(mon.spans()) == 2                    # tee preserved records
    assert mon.aggregator.window(60).count("serve.exec") == 1
    assert mon.aggregator.window(60).gauge("serve.queue_depth") == 2.0
    assert HealthMonitor(clock=clk).spans() == []   # no inner: empty


def test_monitor_verdict_folds_liveness_and_slos():
    clk = VirtualClock()
    mon = HealthMonitor(clock=clk, drift=None,
                        slos=[_err_objective()])
    assert mon.verdict().status == "ok"
    clk.advance(1.0)
    for _ in range(10):
        mon.emit({"name": "serve.error"})
        mon.emit(_exec())
    v = mon.verdict()
    assert v.status == "failing" and any("err" in r for r in v.reasons)
    # a stopped engine fails the verdict regardless of SLO state
    eng = QueryEngine()
    eng.close()
    v = HealthMonitor(clock=VirtualClock(), drift=None).verdict(engine=eng)
    assert v.status == "failing" and "engine stopped" in v.reasons
    assert basic_verdict(eng).status == "failing"


def test_engine_health_without_monitor_is_liveness_only():
    with QueryEngine() as eng:
        assert eng.monitor is None
        assert eng.health().status == "ok"
    assert eng.health().status == "failing"


# ---------------------------------------------------------------------------
# drift detector
# ---------------------------------------------------------------------------


def test_drift_flags_warped_model_quiet_when_calibrated():
    det = DriftDetector(band=4.0, min_count=8, token_fn=lambda: "tok")
    for _ in range(20):
        det.observe("msa", "r1", 1.2)      # calibrated-ish
    assert det.flags() == []
    for _ in range(20):
        det.observe("hash", "r1", 1 / 64)  # modeled 64x too high
    (flag,) = det.flags()
    assert flag.algorithm == "hash" and flag.family == "row"
    assert flag.ewma_residual == pytest.approx(1 / 64, rel=0.05)
    assert "modeled >> measured" in flag.reason
    rep = det.report()
    assert rep.families == ("row",)
    assert "python -m repro.tune --only row" in rep.command
    assert rep.token == "tok"
    assert det.snapshot()["row/hash/r1"]["count"] == 20


def test_drift_needs_min_count_before_flagging():
    det = DriftDetector(band=2.0, min_count=8, token_fn=lambda: "t")
    for _ in range(7):
        det.observe("msa", "r", 100.0)
    assert det.flags() == []               # one short of min_count
    det.observe("msa", "r", 100.0)
    assert len(det.flags()) == 1
    assert det.report().command            # recommendation materializes


def test_drift_resets_on_cost_model_token_change():
    tok = ["t1"]
    det = DriftDetector(band=2.0, min_count=4, token_fn=lambda: tok[0])
    for _ in range(10):
        det.observe("msa", "r", 100.0)
    assert det.flags() and det.token == "t1"
    tok[0] = "t2"                          # retuned table: stats void
    det.observe("msa", "r", 1.0)
    assert det.token == "t2"
    assert det.flags() == []
    assert det.snapshot()["row/msa/r"]["count"] == 1


def test_drift_observe_record_normalizes_by_size_skips_burst():
    det = DriftDetector(band=2.0, min_count=1, token_fn=lambda: "t")
    det.observe_record({"name": "serve.exec", "dur": 8e-3,
                        "attrs": {"modeled_ms": 1.0, "size": 8,
                                  "algorithm": "msa", "route": "batched",
                                  "regime": "r"}})
    st = det.snapshot()["row/msa/r"]
    assert st["count"] == 1
    assert st["ewma_residual"] == pytest.approx(1.0)   # 8ms / (1ms * 8)
    det.observe_record({"name": "serve.exec", "dur": 1.0,
                        "attrs": {"modeled_ms": 1.0, "size": 1,
                                  "algorithm": "msa", "route": "burst",
                                  "regime": "r"}})
    assert det.snapshot()["row/msa/r"]["count"] == 1   # burst skipped
    # non-residual records are ignored, not fatal
    det.observe_record({"name": "serve.submit"})
    det.observe_record({"name": "serve.exec", "counter": 1.0})
    assert det.ingest(None) == 0
    assert det.ingest([_exec()]) == 0                  # no modeled_ms


def test_drift_welford_matches_batch_statistics():
    from repro.obs.drift import KernelStats
    vals = [0.5, 1.0, 2.0, 4.0, 8.0]
    st = KernelStats()
    for v in vals:
        st.update(math.log(v))
    mean = sum(math.log(v) for v in vals) / len(vals)
    var = (sum((math.log(v) - mean) ** 2 for v in vals)
           / (len(vals) - 1))
    assert st.mean == pytest.approx(mean)
    assert st.variance == pytest.approx(var)
    assert st.mean_residual == pytest.approx(math.exp(mean))


def test_family_mapping_covers_kernels():
    assert family_of("msa") == family_of("hash") == "row"
    assert family_of("tile") == "tile"
    assert family_of("spsumma") == "dist"
    assert family_of(None) == "row"        # row kernels are the default
    with pytest.raises(ValueError):
        DriftDetector(band=1.0)


# ---------------------------------------------------------------------------
# planner hooks: feature_regime + bounded explain memo (satellite 1)
# ---------------------------------------------------------------------------


def test_feature_regime_is_stable_and_scale_sensitive():
    A, B, M = _operands(n=64)
    p = planner.plan(A, B, M)
    r1 = planner.feature_regime(p)
    assert isinstance(r1, str) and r1 == planner.feature_regime(p)
    A2, B2, M2 = _operands(n=512, seed=9)
    assert planner.feature_regime(planner.plan(A2, B2, M2)) != r1


def test_explain_memo_registered_and_bounded():
    info = caches.cache_info()
    assert "planner-explain" in info       # cache-registry lint contract
    assert info["planner-explain"]["capacity"] >= 1
    # memoization works and set_capacity bounds it immediately
    A, B, M = _operands(seed=5)
    p = planner.plan(A, B, M)
    assert planner.explain_cached(p) is planner.explain_cached(p)
    old_cap = info["planner-explain"]["capacity"]
    try:
        caches.set_capacity("planner-explain", 1)
        assert caches.cache_info()["planner-explain"]["size"] <= 1
    finally:
        caches.set_capacity("planner-explain", old_cap)


def test_explain_memo_cap_env_var():
    """$REPRO_EXPLAIN_MEMO_CAP bounds the memo at import (subprocess:
    the cache is created when repro.core.planner first loads)."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("from repro import caches; import repro.core.planner; "
            "print(caches.cache_info()['planner-explain']['capacity'])")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               REPRO_EXPLAIN_MEMO_CAP="17", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         cwd=root, timeout=240)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "17"


# ---------------------------------------------------------------------------
# engine + HTTP integration: verdicts on the wire
# ---------------------------------------------------------------------------


def test_induced_pressure_flips_health_to_503_with_reasons():
    A, B, M = _operands(seed=61)
    mon = HealthMonitor(drift=None)
    with QueryEngine(monitor=mon, expose_port=0) as eng:
        base = eng.obs_server.url
        with obs.tracing(mon):
            eng.serve([(A, B, M)] * 4)
            with urllib.request.urlopen(f"{base}/health", timeout=10) as r:
                healthy = json.loads(r.read().decode())
            assert r.status == 200 and healthy["status"] == "ok"
            assert healthy["reasons"] == []
            # hash+complement raises NotImplementedError in the bucket:
            # a deterministic error storm that burns the error budget
            bad = [eng.submit(A, B, M, algorithm="hash", complement=True)
                   for _ in range(16)]
            eng.flush()
            for t in bad:
                with pytest.raises(NotImplementedError):
                    t.result()
            assert eng.health().status == "failing"
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"{base}/health", timeout=10)
            assert exc.value.code == 503
            payload = json.loads(exc.value.read().decode())
            assert payload["status"] == "failing"
            assert any("serve-errors" in r for r in payload["reasons"])


def test_metrics_exposition_gains_slo_and_drift_families():
    A, B, M = _operands(seed=71)
    mon = HealthMonitor()
    mon.drift._token_fn = lambda: "tok"     # hermetic: no planner import
    for _ in range(10):
        mon.drift.observe("msa", "r1", 1 / 64)
    with QueryEngine(monitor=mon, expose_port=0) as eng:
        with obs.tracing(mon):
            eng.serve([(A, B, M)] * 2)
        base = eng.obs_server.url
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
    samples = obs.parse_prometheus(text)
    assert samples[("repro_slo_burn_rate",
                    (("slo", "serve-errors"), ("window", "long")))] == 0.0
    assert samples[("repro_slo_healthy",
                    (("slo", "serve-latency-p99"),))] == 1.0
    assert ("repro_health_status", ()) in samples
    drift_labels = (("algorithm", "msa"), ("family", "row"),
                    ("regime", "r1"))
    assert samples[("repro_drift_observations", drift_labels)] == 10.0
    assert samples[("repro_drift_flagged", drift_labels)] == 1.0
    assert samples[("repro_drift_flagged_families", ())] == 1.0
    assert samples[("repro_drift_ewma_residual", drift_labels)] \
        == pytest.approx(1 / 64, rel=0.05)


def test_render_prometheus_without_monitor_has_no_slo_families():
    with QueryEngine() as eng:
        text = obs.render_prometheus(eng)
    assert "repro_slo_" not in text and "repro_drift_" not in text
