"""Planner subsystem: decision function, plan cache, auto dispatch, batching."""
import dataclasses

import numpy as np
import pytest

from repro.core.formats import (csr_from_dense, erdos_renyi,
                                random_mask_like, rmat)
from repro.core.masked_spgemm import (ALGORITHMS, masked_spgemm,
                                      masked_spgemm_batched)
from repro.core.planner import (PlanStats, clear_plan_cache, collect_stats,
                                decide, plan, plan_batch, plan_cache_info,
                                rank_algorithms)
from repro.core.semiring import MIN_PLUS, PLUS_TIMES

from conftest import SCALE16_TC


def stats(**kw):
    base = dict(m=1024, k=1024, n=1024, nnz_a=9000, nnz_b=9000, nnz_m=9000,
                wa=20, wb=20, wbt=20, pm=20, complement=False)
    base.update(kw)
    return PlanStats(**base)


# ---- decision function: golden table + purity -----------------------------

#: regime -> (stats, acceptable algorithms).  Encodes the paper's Sec. 7-8
#: guidelines as realized by this implementation's cost hooks: Inner for
#: masks sparser than the padded product, MCA for masks much denser than
#: the inputs, MSA for complemented masks, a heap variant for complement +
#: huge n (MSA's dense state init dominates).  heap and heapdot are listed
#: together for complemented regimes: with a complemented mask the inspect
#: path is disabled (``_row_fn`` forces n_inspect=0), so the two names run
#: the IDENTICAL computation and a calibrated cost model may rank either
#: first.  These must hold under any sane calibration profile (the CI tune
#: job re-runs this table under a freshly fitted one).
GOLDEN = {
    "sparse_mask": (stats(nnz_m=3000, pm=4), ("inner",)),
    "dense_mask_sparse_inputs": (
        stats(nnz_a=2000, nnz_b=2000, nnz_m=130000,
              wa=7, wb=8, wbt=9, pm=152), ("mca",)),
    "dense_inputs_mid_mask": (
        stats(nnz_a=33000, nnz_b=33000, wa=52, wb=52, wbt=52, pm=9),
        ("inner",)),
    "complement": (stats(complement=True), ("msa",)),
    "complement_huge_n": (
        stats(m=10**6, k=10**6, n=10**6, nnz_a=2 * 10**6,
              nnz_b=2 * 10**6, nnz_m=4 * 10**6, wa=2, wb=2, wbt=2, pm=4,
              complement=True), ("heap", "heapdot")),
}


@pytest.mark.parametrize("regime", sorted(GOLDEN))
def test_decision_golden_table(regime):
    s, want = GOLDEN[regime]
    assert decide(s).algorithm in want


def test_decision_is_pure_and_deterministic():
    s = GOLDEN["sparse_mask"][0]
    assert decide(s) == decide(s)
    assert rank_algorithms(s) == rank_algorithms(s)


def test_complement_restricts_candidates():
    ranked = [a for a, _ in rank_algorithms(stats(complement=True))]
    assert set(ranked).isdisjoint({"hash", "mca", "inner"})


def test_ranking_covers_all_algorithms():
    ranked = [a for a, _ in rank_algorithms(stats())]
    assert sorted(ranked) == sorted(ALGORITHMS)


def test_memory_limit_drops_kernels_that_cannot_fit():
    """On a 16 GB chip the scale-16 triangle count must not elect a kernel
    whose vmapped workspace exceeds the device: inner (modeled cheapest)
    and msa (21 GB dense state) are dropped, hash/mca remain; on a 4-way
    mesh each shard holds a quarter of the rows, so msa fits again."""
    unlimited = stats(**SCALE16_TC)
    assert decide(unlimited).algorithm == "inner"
    chip = stats(**SCALE16_TC, mem_limit=16 * 10 ** 9)
    ranked = [a for a, _ in rank_algorithms(chip)]
    assert set(ranked) == {"hash", "mca"}
    assert decide(chip).algorithm in ("hash", "mca")
    sharded = [a for a, _ in rank_algorithms(chip, rows=65536 // 4)]
    assert "msa" in sharded and "inner" not in sharded
    with pytest.raises(ValueError, match="no row kernel fits"):
        rank_algorithms(stats(**SCALE16_TC, mem_limit=10 ** 6))


@pytest.mark.parametrize("scale,inner_fits", [(12, True), (13, False)])
def test_memory_limit_on_inner_follows_the_chip_compiler(scale, inner_fits):
    """Compiled for a v5e, inner's program for the R-MAT triangle count
    takes 4.0 GB at scale 12 and 17.0 GB at scale 13, against the chip's
    16.9 GB ``bytes_limit``: the planner keeps it only where it fits."""
    from repro.core.formats import tril
    from repro.graphs.triangle_counting import degree_relabel
    L = tril(degree_relabel(rmat(scale, 16, seed=1)), strict=True)
    s = dataclasses.replace(collect_stats(L, L, L), mem_limit=16_909_336_064)
    assert ("inner" in dict(rank_algorithms(s))) is inner_fits


# ---- tile-path eligibility ------------------------------------------------


def test_tile_eligible_dense_aligned():
    s = stats(m=256, k=256, n=256, nnz_a=5000, nnz_b=5000)
    p = decide(s)
    assert p.tile_eligible and p.tile_block in (8, 32, 128)


@pytest.mark.parametrize("bad", [
    dict(m=250),                      # not MXU-alignable
    dict(complement=True),            # complement: mask does not bound C
    dict(semiring="min_plus"),        # tile kernels are plus_times only
    dict(nnz_a=100, nnz_b=100),       # tiles would be mostly padding
])
def test_tile_ineligible(bad):
    s = stats(m=256, k=256, n=256, nnz_a=5000, nnz_b=5000)
    s = dataclasses.replace(s, **bad)
    assert not decide(s).tile_eligible


# ---- plan cache -----------------------------------------------------------


def test_plan_cache_hit_on_identical_structure():
    clear_plan_cache()
    rng = np.random.default_rng(3)
    A = (rng.random((32, 32)) < 0.2).astype(np.float32)
    B = (rng.random((32, 32)) < 0.2).astype(np.float32)
    M = (rng.random((32, 32)) < 0.3).astype(np.float32)
    p1 = plan(csr_from_dense(A), csr_from_dense(B), csr_from_dense(M))
    assert plan_cache_info() == {"hits": 0, "misses": 1, "size": 1,
                                 "capacity": 128}
    # same structure, different values -> cache hit, identical plan
    p2 = plan(csr_from_dense(A * 2), csr_from_dense(B * 3),
              csr_from_dense(M))
    assert plan_cache_info()["hits"] == 1
    assert p2 is p1
    # different mask structure -> miss
    M2 = M.copy()
    M2[0, 0] = 0.0 if M[0, 0] else 1.0
    plan(csr_from_dense(A), csr_from_dense(B), csr_from_dense(M2))
    assert plan_cache_info()["misses"] == 2
    # complement is part of the key
    plan(csr_from_dense(A), csr_from_dense(B), csr_from_dense(M),
         complement=True)
    assert plan_cache_info()["misses"] == 3


def test_retune_invalidates_cached_plans():
    """Regression (stale-plan bug): the documented retune workflow —
    mutating the cost constants in place — must change what plan()
    returns for an already-planned structure, without an explicit
    clear_plan_cache().  The cache keys include cost_model_token(), so a
    plan decided under the old constants stops matching."""
    from repro.core import accumulators as acc
    from repro.core.planner import TILE_COST, cost_model_token

    clear_plan_cache()
    g = erdos_renyi(64, 4, seed=13)
    m = random_mask_like(g, 0.5, seed=14)
    p1 = plan(g, g, m)
    assert plan_cache_info()["misses"] == 1
    token_before = cost_model_token()
    # retune: make the chosen algorithm ruinously expensive
    table = (TILE_COST if p1.algorithm == "tile"
             else acc.COST_CONSTANTS[p1.algorithm])
    old = table["base"]
    try:
        table["base"] = old + 1e9
        assert cost_model_token() != token_before
        p2 = plan(g, g, m)
        assert plan_cache_info()["misses"] == 2, \
            "plan served from cache despite retuned constants"
        assert p2.algorithm != p1.algorithm
    finally:
        table["base"] = old
    # restored constants -> original key -> cache hit again
    assert plan(g, g, m) is p1


def test_collect_stats_widths_are_exact():
    g = erdos_renyi(128, 4, seed=9)
    m = random_mask_like(g, 0.5, seed=10)
    s = collect_stats(g, g, m)
    assert s.wa == int(np.diff(g.indptr).max())
    assert s.wbt == int(np.bincount(g.indices, minlength=128).max())
    assert s.pm == int(np.diff(m.indptr).max())
    assert s.flops > 0 and s.out_nnz >= 0 and s.compression >= 1.0


# ---- auto dispatch --------------------------------------------------------


def test_auto_matches_every_fixed_algorithm_bitwise():
    """On a 0/1 R-MAT instance every algorithm computes integer counts, so
    auto must agree with each fixed algorithm bit-for-bit."""
    g = rmat(7, 4, seed=5)
    m = random_mask_like(g, 0.6, seed=6)
    auto = masked_spgemm(g, g, m, algorithm="auto")
    dense_auto = np.asarray(auto.to_dense())
    for algorithm in ALGORITHMS:
        fixed = masked_spgemm(g, g, m, algorithm=algorithm)
        np.testing.assert_array_equal(dense_auto,
                                      np.asarray(fixed.to_dense()))
        np.testing.assert_array_equal(np.asarray(auto.present),
                                      np.asarray(fixed.present))


def test_auto_complement_picks_supported_algorithm():
    g = rmat(6, 4, seed=7)
    m = random_mask_like(g, 0.5, seed=8)
    p = plan(g, g, m, complement=True)
    assert p.algorithm in ("msa", "heap", "heapdot")
    vals, present = masked_spgemm(g, g, m, algorithm="auto",
                                  complement=True)
    want_v, want_p = masked_spgemm(g, g, m, algorithm="msa",
                                   complement=True)
    np.testing.assert_array_equal(np.asarray(present), np.asarray(want_p))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_v))


def test_auto_respects_semiring_in_cache_key():
    clear_plan_cache()
    g = erdos_renyi(64, 4, seed=11)
    m = random_mask_like(g, 0.5, seed=12)
    plan(g, g, m, semiring=PLUS_TIMES)
    plan(g, g, m, semiring=MIN_PLUS)
    assert plan_cache_info()["misses"] == 2


# ---- batched driver -------------------------------------------------------


def test_batched_matches_per_item():
    rng = np.random.default_rng(21)
    B = csr_from_dense(((rng.random((24, 20)) < 0.3) * 1.0
                        ).astype(np.float32))
    As = [csr_from_dense(((rng.random((16, 24)) < 0.3)
                          * rng.uniform(0.5, 1.5, (16, 24))
                          ).astype(np.float32)) for _ in range(4)]
    Ms = [csr_from_dense(((rng.random((16, 20)) < 0.4) * 1.0
                          ).astype(np.float32)) for _ in range(4)]
    batched = masked_spgemm_batched(As, B, Ms)
    for a, m, r in zip(As, Ms, batched):
        single = masked_spgemm(a, B, m, algorithm="auto")
        np.testing.assert_allclose(np.asarray(r.to_dense()),
                                   np.asarray(single.to_dense()),
                                   rtol=1e-5, atol=1e-6)


def test_batched_complement_matches_per_item():
    rng = np.random.default_rng(22)
    B = csr_from_dense(((rng.random((12, 12)) < 0.3) * 1.0
                        ).astype(np.float32))
    As = [csr_from_dense(((rng.random((8, 12)) < 0.3) * 1.0
                          ).astype(np.float32)) for _ in range(3)]
    Ms = [csr_from_dense(((rng.random((8, 12)) < 0.4) * 1.0
                          ).astype(np.float32)) for _ in range(3)]
    vals, present = masked_spgemm_batched(As, B, Ms, complement=True)
    p = plan_batch(As, B, Ms, complement=True)
    for i, (a, m) in enumerate(zip(As, Ms)):
        wv, wp = masked_spgemm(a, B, m, algorithm=p.algorithm,
                               complement=True)
        np.testing.assert_array_equal(np.asarray(present[i]),
                                      np.asarray(wp))
        np.testing.assert_allclose(np.asarray(vals[i]), np.asarray(wv),
                                   rtol=1e-5, atol=1e-6)


def test_plan_batch_widens_to_batch_maxima():
    rng = np.random.default_rng(23)
    dense = [((rng.random((10, 10)) < d) * 1.0).astype(np.float32)
             for d in (0.1, 0.6)]
    As = [csr_from_dense(x) for x in dense]
    Ms = [csr_from_dense((x != 0).astype(np.float32)) for x in dense]
    B = csr_from_dense(((rng.random((10, 10)) < 0.3) * 1.0
                        ).astype(np.float32))
    p = plan_batch(As, B, Ms)
    assert p.widths[0] == max(int(np.diff(a.indptr).max()) for a in As)
    assert p.widths[2] == max(int(np.diff(m.indptr).max()) for m in Ms)


# ---- distributed decision: row-parallel vs sparse ring --------------------


def test_decide_distributed_lists_and_ranks_routes():
    from repro.core.planner import decide_distributed, distributed_costs
    s = stats()
    for p in (2, 4, 8):
        d = decide_distributed(s, p)
        assert d.route in ("row", "ring")
        assert d.p == p and d.tile_block in (8, 32, 128)
        names = [name for name, _ in d.costs]
        assert "row" in names and "ring" in names
        vals = [c for _, c in d.costs]
        assert vals == sorted(vals)
        assert distributed_costs(s, p) == d.costs


def test_decide_distributed_respects_tile_support():
    """Non-plus_times or complemented products cannot ride the ring: the
    decision must fall back to the row route and not even list ring."""
    from repro.core.planner import decide_distributed
    for bad in (stats(semiring="min_plus"), stats(complement=True)):
        d = decide_distributed(bad, 4)
        assert d.route == "row"
        assert [name for name, _ in d.costs] == ["row"]
        assert d.tile_block == 0


def test_decide_distributed_prefers_ring_when_b_is_huge():
    """A B too fat to replicate (huge padded width) must push auto off the
    row route: replication bytes scale with k * wb while the ring only
    moves occupied slabs."""
    from repro.core.planner import decide_distributed
    s = stats(m=4096, k=4096, n=4096, nnz_a=4096 * 410, nnz_b=4096 * 410,
              nnz_m=4096 * 410, wa=512, wb=4096, wbt=4096, pm=512)
    d = decide_distributed(s, 8)
    assert d.cost("ring") < d.cost("row")
    assert d.route == "ring"


def test_slab_schedules_partition_the_full_schedule():
    """Per-slab worklists must partition the full schedule's real entries:
    same total MAC count, same per-rank contribution counts."""
    from repro.core.formats import (bcsr_from_csr, bcsr_pad_block_rows,
                                    bcsr_row_panels)
    from repro.kernels.masked_matmul.ops import (build_spgemm_schedule,
                                                 build_spgemm_schedule_slab)
    rng = np.random.default_rng(31)
    dense = lambda m, n, d: ((rng.random((m, n)) < d) * 1.0
                             ).astype(np.float32)
    A = bcsr_from_csr(csr_from_dense(dense(40, 48, 0.2)), 8)
    B = bcsr_from_csr(csr_from_dense(dense(48, 40, 0.2)), 8)
    M = bcsr_from_csr(csr_from_dense(dense(40, 40, 0.4)), 8)
    rank, _, _, flags = build_spgemm_schedule(A, B, M)
    want = np.bincount(rank[((flags >> 1) & 1) == 1], minlength=M.nnzb)
    p = 4
    slabs = bcsr_row_panels(
        bcsr_pad_block_rows(B, -(-B.block_rows // p) * p), p)
    rows_per = slabs[0].block_rows
    got = np.zeros(M.nnzb, np.int64)
    for s, slab in enumerate(slabs):
        r, pa, pb, fl = build_spgemm_schedule_slab(A, slab, M, s * rows_per)
        real = ((fl >> 1) & 1) == 1
        got += np.bincount(r[real], minlength=M.nnzb)
        assert (np.diff(r) >= 0).all()        # rank-sorted per stage
        assert pb.max(initial=0) <= max(0, slab.nnzb - 1)
    np.testing.assert_array_equal(got, want)
