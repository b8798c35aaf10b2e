"""Health-intelligence gate: monitored serving must stay (nearly) free.

PR 10 attaches a :class:`repro.obs.health.HealthMonitor` — streaming
window aggregation + SLO burn rates + cost-model drift detection — to
the span stream.  This bench holds that machinery to its claims and
writes ``results/bench/health_grid.json``:

* ``overhead`` — one stream served by the SAME engine alternately under
  a plain in-memory sink and under a HealthMonitor: monitored tracing
  must stay within ``OVERHEAD_TOLERANCE`` of plain tracing, results
  bitwise equal and ``deterministic_snapshot()`` EQUAL between a
  plain-traced and a monitor-traced engine (``_health_ok``);
* ``pressure`` — a live engine reports /health 200 "ok"; a deterministic
  error storm (hash+complement is NotImplemented) must burn the error
  budget and flip /health to 503 with concrete reasons
  (``_pressure_ok``);
* ``drift`` — a calibrated cost table stays quiet, then the same table
  warped x256 must trip the detector with the matching
  ``repro.tune --only`` recommendation (``_drift_ok``).
"""
from __future__ import annotations

import json
import urllib.error
import urllib.request
from typing import Dict

import numpy as np

from repro import obs
from repro.core import accumulators as acc
from repro.core.formats import CSR, er_mask, erdos_renyi
from repro.obs.drift import DriftDetector
from repro.obs.health import HealthMonitor
from repro.obs.sinks import InMemorySink
from repro.serving import QueryEngine

from .bench_obs import OVERHEAD_TOLERANCE, _bitwise_equal, _serve, _timed_pair
from .common import save

#: multiplicative warp applied to every cost constant in the drift
#: scenario — far outside the detector band, so the verdict is
#: unambiguous even with cold-compile outliers in the stream
DRIFT_WARP = 256.0

#: detector band for the bench: wide enough that an honestly calibrated
#: table (residuals within ~2x plus decaying cold-start outliers) stays
#: quiet on any CI host, narrow enough that a x256 warp trips instantly
DRIFT_BAND = 8.0


def _revalue(x: CSR, seed: int) -> CSR:
    rng = np.random.default_rng(seed)
    return CSR(x.indptr, x.indices,
               rng.uniform(0.5, 1.5, x.nnz).astype(np.float32), x.shape)


def _burst(n: int, queries: int, seed: int = 0):
    A0 = erdos_renyi(n, 2, seed=100 + seed)
    B0 = erdos_renyi(n, 2, seed=200 + seed)
    M0 = er_mask(n, max(8, n // 8), seed=300 + seed)
    return [(_revalue(A0, 1000 + seed + q), B0, M0) for q in range(queries)]


def run(n: int = 1024, queries: int = 96, iters: int = 61,
        smoke: bool = False) -> Dict:
    table: Dict = {}

    # ---- monitored vs plain-traced serve throughput -----------------------
    # The PR 9 gate already bounds tracing vs untraced; this one bounds the
    # *aggregation* increment: the same stream, the same engine, traced
    # into a bare InMemorySink (A) vs a HealthMonitor (B).  Same timing
    # discipline as bench_obs (same engine both callbacks, alternation,
    # midmean of paired ratios) — see _timed_pair for why.
    stream = _burst(n, queries)
    plain = QueryEngine(cache_results=False)
    monitored = QueryEngine(cache_results=False)
    mon_check = HealthMonitor(inner=InMemorySink(capacity=16384))
    try:
        with obs.tracing(InMemorySink(capacity=16384)):
            want = _serve(plain, stream)
        with obs.tracing(mon_check):
            got = _serve(monitored, stream)
        bitwise_ok = all(_bitwise_equal(g, w) for g, w in zip(got, want))
        snap_equal = (plain.metrics.deterministic_snapshot()
                      == monitored.metrics.deterministic_snapshot())
        agg_names = mon_check.aggregator.window(60).names

        sink = InMemorySink(capacity=16384)
        mon_timed = HealthMonitor()           # aggregation + drift, no tee

        def plain_pass():
            with obs.tracing(sink):
                _serve(plain, stream)

        def monitored_pass():
            with obs.tracing(mon_timed):
                _serve(plain, stream)

        t_plain, t_mon = _timed_pair(plain_pass, monitored_pass, iters)
        overhead = t_mon / max(t_plain, 1e-12) - 1.0
        health_ok = (overhead <= OVERHEAD_TOLERANCE and bitwise_ok
                     and snap_equal)
        table["overhead"] = {
            "n": n, "queries": queries, "iters": iters,
            "plain_traced_s": t_plain, "monitored_s": t_mon,
            "plain_qps": queries / max(t_plain, 1e-12),
            "monitored_qps": queries / max(t_mon, 1e-12),
            "overhead_frac": overhead, "tolerance": OVERHEAD_TOLERANCE,
            "window_names": agg_names,
            "bitwise_equal": bitwise_ok,
            "deterministic_snapshot_equal": snap_equal,
        }
        print(f"[health] overhead n={n} q={queries}: plain "
              f"{t_plain * 1e3:7.1f}ms monitored {t_mon * 1e3:7.1f}ms "
              f"(+{overhead * 100:.2f}%, bar "
              f"{OVERHEAD_TOLERANCE * 100:.0f}%) bitwise="
              f"{'OK' if bitwise_ok else 'FAIL'} snap_eq={snap_equal}",
              flush=True)
    finally:
        plain.close()
        monitored.close()

    # ---- induced pressure: /health flips to 503-with-reasons --------------
    press_n = 64 if smoke else 256
    monitor = HealthMonitor(drift=None)
    engine = QueryEngine(monitor=monitor, expose_port=0)
    try:
        base = engine.obs_server.url
        with obs.tracing(monitor):
            _serve(engine, _burst(press_n, 8, seed=7))
            with urllib.request.urlopen(f"{base}/health", timeout=10) as r:
                healthy = json.loads(r.read().decode("utf-8"))
                healthy_code = r.status
            A, B, M = _burst(press_n, 1, seed=7)[0]
            storm = [engine.submit(A, B, M, algorithm="hash",
                                   complement=True) for _ in range(16)]
            engine.flush()
            failures = 0
            for t in storm:
                try:
                    t.result()
                except NotImplementedError:
                    failures += 1
            verdict = engine.health()
            try:
                urllib.request.urlopen(f"{base}/health", timeout=10)
                failing_code, failing = 200, {}
            except urllib.error.HTTPError as e:
                failing_code = e.code
                failing = json.loads(e.read().decode("utf-8"))
        pressure_ok = (healthy_code == 200 and healthy["status"] == "ok"
                       and failures == 16
                       and verdict.status == "failing"
                       and failing_code == 503
                       and failing.get("status") == "failing"
                       and any("serve-errors" in r
                               for r in failing.get("reasons", ())))
        table["pressure"] = {
            "healthy_code": healthy_code, "healthy": healthy,
            "induced_failures": failures,
            "failing_code": failing_code, "failing": failing,
        }
        print(f"[health] pressure {healthy_code} -> {failing_code} "
              f"({failures} induced failures, verdict={verdict.status}, "
              f"reasons={len(failing.get('reasons', ()))})", flush=True)
    finally:
        engine.close()

    # ---- cost-model drift: warped table trips, calibrated stays quiet ----
    drift_n = 64 if smoke else 256
    drift_q = 16 if smoke else 24
    det = DriftDetector(band=DRIFT_BAND)
    drift_mon = HealthMonitor(drift=det)
    # max_batch=1 + use_burst=False: every query is its own non-burst
    # exec span, so the per-query cost model prices exactly what the
    # span measures (burst replays are skipped by design)
    engine = QueryEngine(max_batch=1, use_burst=False, cache_results=False,
                         monitor=drift_mon)
    originals = {k: dict(v) for k, v in acc.COST_CONSTANTS.items()}
    try:
        with obs.tracing(drift_mon):
            _serve(engine, _burst(drift_n, drift_q, seed=11))
        quiet_flags = det.flags()
        quiet_stats = {k: dict(count=v["count"],
                               ewma_residual=v["ewma_residual"])
                       for k, v in det.snapshot().items()}
        # warp the LIVE table: cost_model_token() changes, the detector
        # resets (old residuals say nothing about the new model) and the
        # fresh residuals land ~1/DRIFT_WARP
        for name, consts in acc.COST_CONSTANTS.items():
            for k in consts:
                consts[k] = originals[name][k] * DRIFT_WARP
        with obs.tracing(drift_mon):
            _serve(engine, _burst(drift_n, drift_q, seed=11))
        warped_flags = det.flags()
        rep = det.report()
        drift_ok = (not quiet_flags and len(warped_flags) >= 1
                    and "row" in rep.families
                    and "python -m repro.tune --only" in rep.command)
        table["drift"] = {
            "band": DRIFT_BAND, "warp": DRIFT_WARP,
            "queries_per_phase": drift_q,
            "quiet_flags": len(quiet_flags),
            "quiet_stats": quiet_stats,
            "warped_flags": [f.as_dict() for f in warped_flags],
            "recommendation": rep.command,
        }
        print(f"[health] drift quiet={len(quiet_flags)} flags, warped="
              f"{len(warped_flags)} flags, families={list(rep.families)}",
              flush=True)
        if warped_flags:
            print(f"[health]   {rep.command}", flush=True)
    finally:
        for name, consts in acc.COST_CONSTANTS.items():
            consts.clear()
            consts.update(originals[name])
        engine.close()

    table["_health_ok"] = bool(health_ok)
    table["_pressure_ok"] = bool(pressure_ok)
    table["_drift_ok"] = bool(drift_ok)
    save("health_grid", table)
    return table


if __name__ == "__main__":
    run()
