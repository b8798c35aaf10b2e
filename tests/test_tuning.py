"""Calibration subsystem: profile round-trip, registry, fit, activation.

Every test that activates a profile restores the shipped constants in a
``finally`` — the planner's tables are process-global, and the rest of
the suite golden-tests decisions made under the defaults.
"""
import dataclasses
import json
import subprocess
import sys

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.core import accumulators as acc
from repro.core import planner
from repro.core.formats import random_mask_like, rmat
from repro.core.masked_spgemm import masked_spgemm
from repro.tuning import (CalibrationProfile, ProfileError, activate,
                          active_version, lookup, profile_path, register,
                          snapshot)
from repro.tuning.fit import fit_dist, fit_profile, fit_row, fit_tile
from repro.tuning.probes import Measurement

#: the shipped tables, captured before any test mutates them
BUILTIN = snapshot(name="builtin-for-tests",
                   backend={"platform": "test", "device_kind": "test",
                            "device_count": 1})


def restore_builtin():
    activate(BUILTIN)
    planner.clear_plan_cache()


def perturbed(name="perturbed", scale=3.0, version=""):
    """A structurally valid profile with rescaled constants (a stand-in
    for a fit on very different hardware)."""
    return CalibrationProfile(
        name=name,
        backend=dict(BUILTIN.backend),
        cost_constants={alg: {k: v * scale for k, v in tbl.items()}
                        for alg, tbl in BUILTIN.cost_constants.items()},
        tile_cost={k: v * scale for k, v in BUILTIN.tile_cost.items()},
        tile_gates=dict(BUILTIN.tile_gates),
        dist_cost={k: v * scale for k, v in BUILTIN.dist_cost.items()},
        residuals={"row": 0.1},
        version=version,
    )


# ---- serialization round-trip ---------------------------------------------


@settings(max_examples=20)
@given(scale=st.floats(min_value=0.05, max_value=20.0),
       gate=st.floats(min_value=0.001, max_value=0.5),
       residual=st.floats(min_value=0.0, max_value=10.0))
def test_profile_json_round_trip(scale, gate, residual):
    p = perturbed(scale=scale)
    # version="" makes __post_init__ re-fingerprint the edited tables
    # (dataclasses.replace would otherwise carry the stale explicit token)
    p = dataclasses.replace(p, tile_gates=dict(p.tile_gates,
                                               min_density=gate),
                            residuals={"row": residual, "tile": residual},
                            version="")
    q = CalibrationProfile.from_json(p.to_json())
    assert q == p
    assert q.version == p.version == p.fingerprint()
    # serialization is canonical: a second round trip is byte-identical
    assert q.to_json() == p.to_json()


def test_version_token_tracks_constants():
    assert perturbed(scale=2).version != perturbed(scale=3).version
    assert perturbed(scale=2).version == perturbed(scale=2).version
    assert perturbed(version="pinned").version == "pinned"


@pytest.mark.parametrize("corrupt", [
    lambda d: d.pop("cost_constants"),
    lambda d: d["cost_constants"]["msa"].pop("per_flop"),
    lambda d: d["tile_cost"].update(per_mac=float("nan")),
    lambda d: d["dist_cost"].update(stage_base=-1.0),
    lambda d: d["residuals"].update(row=float("inf")),
    lambda d: d.update(schema=99),
])
def test_profile_validation_rejects(corrupt):
    d = json.loads(perturbed().to_json())
    corrupt(d)
    with pytest.raises(ProfileError):
        CalibrationProfile.from_json(json.dumps(d))


def test_profile_rejects_non_json():
    with pytest.raises(ProfileError):
        CalibrationProfile.from_json("not json {")


# ---- registry -------------------------------------------------------------


def test_registry_hit_miss_and_default_fallback(tmp_path):
    d = str(tmp_path)
    fitted = perturbed(name="tpu-fit")
    fitted = dataclasses.replace(fitted, backend={
        "platform": "tpu", "device_kind": "TPU v4", "device_count": 8})
    register(fitted, d)
    # hit: exact backend signature
    got, exact = lookup(fitted.backend, d)
    assert exact and got == fitted
    # miss without a default: explicit error
    other = {"platform": "gpu", "device_kind": "H100", "device_count": 2}
    with pytest.raises(FileNotFoundError):
        lookup(other, d)
    # miss with a default: falls back, flagged as inexact
    (tmp_path / "default.json").write_text(
        dataclasses.replace(BUILTIN, name="default").to_json())
    got, exact = lookup(other, d)
    assert not exact and got.name == "default"


def test_registry_key_is_filesystem_safe():
    path = profile_path({"platform": "tpu", "device_kind": "TPU v5e/lite:2",
                         "device_count": 16}, "/x")
    name = path.rsplit("/", 1)[1]
    assert name == "tpu_TPU-v5e-lite-2_16.json"


def test_committed_default_profile_matches_shipped_constants():
    """results/profiles/default.json must load, validate, and fingerprint
    identically to the in-code tables — regenerate it with
    ``python -m repro.tune --export-defaults results/profiles/default.json``
    whenever the shipped constants change."""
    p = CalibrationProfile.load("results/profiles/default.json")
    p.validate()
    assert p.fingerprint() == BUILTIN.fingerprint(), (
        "committed default profile is stale vs the shipped constants")


# ---- fit: synthetic ground truth ------------------------------------------


def _row_measurements(gt, n_points=12, noise=0.0, seed=0):
    rng = np.random.default_rng(seed)
    ms = []
    for i in range(n_points):
        s = planner.PlanStats(
            m=int(rng.integers(128, 2048)), k=1024, n=int(2 ** rng.integers(8, 13)),
            nnz_a=9000, nnz_b=9000, nnz_m=9000,
            wa=int(rng.integers(2, 64)), wb=int(rng.integers(2, 64)),
            wbt=int(rng.integers(2, 64)), pm=int(rng.integers(2, 128)),
            complement=False)
        feats = dataclasses.asdict(s)
        for alg, fn in acc.COST_FEATURES.items():
            f = fn(n=s.n, wa=s.wa, wb=s.wb, wbt=s.wbt, pm=s.pm)
            ms_total = sum(gt[alg][k] * f[k] for k in f) * (s.m / 1024.0)
            ms_total *= 1.0 + noise * float(rng.uniform(-1, 1))
            ms.append(Measurement("row", alg, f"syn{i}", ms_total / 1e3,
                                  feats))
    return ms


def test_fit_row_recovers_ground_truth_predictions():
    gt = {alg: {k: v * 2.5 for k, v in tbl.items()}
          for alg, tbl in BUILTIN.cost_constants.items()}
    fitted, resid = fit_row(_row_measurements(gt, noise=0.02),
                            BUILTIN.cost_constants)
    assert np.isfinite(resid) and resid < 0.1
    # held-out prediction check: fitted model ~= ground-truth model
    for m in _row_measurements(gt, n_points=4, seed=99):
        f = acc.COST_FEATURES[m.target](
            n=int(m.features["n"]), wa=int(m.features["wa"]),
            wb=int(m.features["wb"]), wbt=int(m.features["wbt"]),
            pm=int(m.features["pm"]))
        pred = sum(fitted[m.target][k] * f[k] for k in f) \
            * (m.features["m"] / 1024.0)
        assert pred == pytest.approx(m.seconds * 1e3, rel=0.25)


def _tile_measurements(gt_cost, seed=0):
    rng = np.random.default_rng(seed)
    ms = []
    for i in range(10):
        n = 512
        bs = int(rng.choice([8, 16, 32]))
        dens = float(rng.uniform(0.02, 0.4))
        nnz = int(dens * n * n)
        s = planner.PlanStats(m=n, k=n, n=n, nnz_a=nnz, nnz_b=nnz,
                              nnz_m=nnz, wa=8, wb=8, wbt=8, pm=8,
                              complement=False, flops=1e5, out_nnz=1e4)
        f = planner.tile_cost_features(s, bs)
        t_ms = sum(gt_cost[k] * f[k] for k in f)
        feats = dict(dataclasses.asdict(s), bs=float(bs))
        ms.append(Measurement("tile", "tile", f"syn{i}", t_ms / 1e3, feats))
        # row reference: tile wins iff dense (drives the gate fit)
        t_row = t_ms * (0.5 if dens < 0.1 else 2.0)
        ms.append(Measurement("tile", "row:msa", f"syn{i}", t_row / 1e3,
                              feats))
    return ms


def test_fit_tile_recovers_cost_and_moves_gates_only_on_separation():
    gt = {k: v * 4.0 for k, v in BUILTIN.tile_cost.items()}
    cost, gates, resid = fit_tile(_tile_measurements(gt),
                                  BUILTIN.tile_cost, BUILTIN.tile_gates)
    assert np.isfinite(resid) and resid < 0.2
    s = planner.PlanStats(m=512, k=512, n=512, nnz_a=30000, nnz_b=30000,
                          nnz_m=30000, wa=8, wb=8, wbt=8, pm=8,
                          complement=False)
    f = planner.tile_cost_features(s, 16)
    want = sum(gt[k] * f[k] for k in f)
    got = sum(cost[k] * f[k] for k in f)
    assert got == pytest.approx(want, rel=0.2)
    # synthetic outcomes separate exactly at density 0.1 (tile wins the
    # denser points), so the density gate moves to the boundary...
    assert 0.03 <= gates["min_density"] <= 0.25
    # ...while min_hit_rate has no probe signal and is always inherited
    assert gates["min_hit_rate"] == BUILTIN.tile_gates["min_hit_rate"]


def test_fit_dist_finite_and_nonnegative():
    s = planner.PlanStats(m=1024, k=1024, n=1024, nnz_a=90000, nnz_b=90000,
                          nnz_m=90000, wa=128, wb=128, wbt=128, pm=128,
                          complement=False)
    feats = dataclasses.asdict(s)
    gt = BUILTIN.dist_cost
    ms = []
    for p in (2, 4, 8):
        tile_f, comm_f = planner.ring_cost_features(s, p, 32)
        t_ring = (sum(BUILTIN.tile_cost[k] * tile_f[k] for k in tile_f)
                  + sum(gt[k] * comm_f[k] for k in comm_f))
        f_row = acc.COST_FEATURES["msa"](n=s.n, wa=s.wa, wb=s.wb,
                                         wbt=s.wbt, pm=s.pm)
        t_row = (sum(BUILTIN.cost_constants["msa"][k] * f_row[k]
                     for k in f_row) / p
                 + gt["per_bcast_elem"]
                 * planner.row_replication_elems(s, "msa"))
        extra = dict(feats, p=float(p), bs=32.0, row_algorithm="msa")
        ms.append(Measurement("dist", "ring", f"p{p}", t_ring / 1e3, extra))
        ms.append(Measurement("dist", "row", f"p{p}", t_row / 1e3, extra))
    fitted, resid = fit_dist(ms, BUILTIN.cost_constants, BUILTIN.tile_cost,
                             BUILTIN.dist_cost)
    assert np.isfinite(resid)
    assert all(np.isfinite(v) and v >= 0 for v in fitted.values())


def test_fit_profile_inherits_unfitted_families():
    gt = {alg: dict(tbl) for alg, tbl in BUILTIN.cost_constants.items()}
    prof = fit_profile(_row_measurements(gt, n_points=6), BUILTIN,
                       families=("row",), name="row-only",
                       backend=dict(BUILTIN.backend))
    assert prof.tile_cost == BUILTIN.tile_cost
    assert prof.dist_cost == BUILTIN.dist_cost
    assert "row" in prof.residuals and np.isfinite(prof.residuals["row"])
    assert prof.meta["fitted_families"] == ["row"]
    with pytest.raises(ProfileError):
        fit_profile([], BUILTIN, families=("bogus",))


# ---- activation semantics -------------------------------------------------


def test_activation_changes_live_tables_and_token_then_restores():
    try:
        before = planner.cost_model_token()
        activate(perturbed(scale=7.0))
        assert planner.cost_model_token() != before
        assert acc.COST_CONSTANTS["msa"]["base"] == \
            BUILTIN.cost_constants["msa"]["base"] * 7.0
        assert planner.TILE_COST["base"] == BUILTIN.tile_cost["base"] * 7.0
        assert planner.DIST_COST["stage_base"] == \
            BUILTIN.dist_cost["stage_base"] * 7.0
        assert active_version() == perturbed(scale=7.0).version
    finally:
        restore_builtin()


def test_activating_different_version_token_invalidates_cached_plans():
    """Acceptance: same constants + different version token must still
    re-plan — the token alone keys the cache."""
    g = rmat(6, 4, seed=3)
    m = random_mask_like(g, 0.5, seed=4)
    try:
        activate(perturbed(scale=1.0, version="token-a"))
        planner.clear_plan_cache()
        planner.plan(g, g, m)
        assert planner.plan_cache_info()["misses"] == 1
        planner.plan(g, g, m)
        assert planner.plan_cache_info()["hits"] == 1
        activate(perturbed(scale=1.0, version="token-b"))
        planner.plan(g, g, m)
        info = planner.plan_cache_info()
        assert info["misses"] == 2, "stale plan served across activation"
    finally:
        restore_builtin()


def test_masked_spgemm_bitwise_equal_under_default_vs_fitted_profile():
    """Calibration may change WHICH algorithm runs, never WHAT it
    returns: auto results under a freshly 'fitted' (here: heavily
    perturbed) profile must be bitwise those under the default."""
    g = rmat(7, 4, seed=11)
    m = random_mask_like(g, 0.6, seed=12)
    base = masked_spgemm(g, g, m, algorithm="auto")
    base_dense = np.asarray(base.to_dense())
    # invert the relative ranking as hard as a real refit ever could:
    # make each algorithm's dominant term cheap/expensive in opposition
    warped = perturbed(scale=1.0)
    for i, (alg, tbl) in enumerate(sorted(
            warped.cost_constants.items())):
        for k in tbl:
            tbl[k] *= 100.0 if i % 2 else 0.01
    warped = dataclasses.replace(warped, version="warped")
    try:
        activate(warped)
        other = masked_spgemm(g, g, m, algorithm="auto")
        np.testing.assert_array_equal(base_dense,
                                      np.asarray(other.to_dense()))
        np.testing.assert_array_equal(np.asarray(base.present),
                                      np.asarray(other.present))
    finally:
        restore_builtin()


def test_env_var_activates_profile_in_child_process(tmp_path):
    p = perturbed(scale=5.0, version="env-test")
    path = str(tmp_path / "env_profile.json")
    p.save(path)
    code = (
        "import repro.core.planner as pl, repro.core.accumulators as acc, "
        "repro.tuning as tu\n"
        "assert tu.active_version() == 'env-test', tu.active_version()\n"
        f"assert acc.COST_CONSTANTS['msa']['base'] == "
        f"{BUILTIN.cost_constants['msa']['base'] * 5.0!r}\n"
        "print('ok', pl.cost_model_token())\n")
    import os
    env = dict(os.environ, PYTHONPATH="src", REPRO_TUNE_PROFILE=path,
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.startswith("ok env-test-")


def test_measure_in_process_never_spawns_a_chip_child(monkeypatch):
    """Enough devices: measure here.  Too few on the CPU: a forced-host
    child stands in.  Too few on an accelerator: raise, since a child
    would need the chip this process holds."""
    import jax
    from repro.tuning.probes import measure_in_process
    have = len(jax.devices())
    assert measure_in_process(have) is True
    assert measure_in_process(have + 1) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="devices needed"):
        measure_in_process(have + 1)
