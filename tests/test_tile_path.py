"""BCSR tile pipeline: densify-free converters, vectorized schedule, and the
tile route's bitwise agreement with the row kernels and the dense oracle.

Value matrices use small random *integers* so every summation order is exact
in float32 — "bitwise" here means array_equal, not allclose.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container has no hypothesis; deterministic fallback
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.formats import (bcsr_block_positions, bcsr_from_csr,
                                bcsr_from_dense, bcsr_to_csr, csr_from_dense)
from repro.core.masked_spgemm import dense_oracle, masked_spgemm
from repro.core.planner import clear_plan_cache, plan
from repro.kernels.masked_matmul import ops
from repro.kernels.masked_matmul.ops import (block_spgemm,
                                             build_spgemm_schedule,
                                             block_spgemm_from_csr)


def int_sparse(rng, m, n, density):
    """Sparse float32 matrix with small integer values (exact summation)."""
    return ((rng.random((m, n)) < density)
            * rng.integers(1, 5, (m, n))).astype(np.float32)


# ---------------------------------------------------------------------------
# converters
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10 ** 6), m=st.integers(1, 40),
       n=st.integers(1, 40), bs=st.sampled_from([2, 4, 8, 16]),
       density=st.floats(0.0, 0.6))
def test_bcsr_from_csr_to_csr_roundtrip(seed, m, n, bs, density):
    rng = np.random.default_rng(seed)
    a = int_sparse(rng, m, n, density)
    c = csr_from_dense(a)
    b = bcsr_from_csr(c, bs)
    back = bcsr_to_csr(b)
    np.testing.assert_array_equal(back.to_dense(), a)
    np.testing.assert_array_equal(back.indptr, c.indptr)
    np.testing.assert_array_equal(back.indices, c.indices)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10 ** 6), m=st.integers(1, 33),
       n=st.integers(1, 33), bs=st.sampled_from([4, 8]))
def test_bcsr_from_csr_matches_from_dense(seed, m, n, bs):
    """The direct scatter builds byte-identical structure and blocks to the
    densify-and-reblock reference."""
    rng = np.random.default_rng(seed)
    a = int_sparse(rng, m, n, 0.25)
    b1 = bcsr_from_csr(csr_from_dense(a), bs)
    b2 = bcsr_from_dense(a, bs)
    np.testing.assert_array_equal(b1.indptr, b2.indptr)
    np.testing.assert_array_equal(b1.indices, b2.indices)
    np.testing.assert_array_equal(np.asarray(b1.blocks),
                                  np.asarray(b2.blocks))


def test_bcsr_block_positions_lookup():
    rng = np.random.default_rng(5)
    b = bcsr_from_csr(csr_from_dense(int_sparse(rng, 30, 30, 0.2)), 8)
    brow = np.repeat(np.arange(b.block_rows), np.diff(b.indptr))
    np.testing.assert_array_equal(
        bcsr_block_positions(b, brow, b.indices), np.arange(b.nnzb))
    # absent blocks come back -1
    occupied = set(zip(brow.tolist(), b.indices.tolist()))
    absent = [(i, j) for i in range(b.block_rows)
              for j in range(b.block_cols) if (i, j) not in occupied][:4]
    if absent:
        bi, bj = np.array(absent).T
        assert (bcsr_block_positions(b, bi, bj) == -1).all()


# ---------------------------------------------------------------------------
# schedule + executors
# ---------------------------------------------------------------------------


def test_schedule_empty_mask_and_empty_block_spgemm():
    """M.nnzb == 0 is a defined degenerate: empty worklist, empty output,
    no kernel launch."""
    rng = np.random.default_rng(1)
    A = bcsr_from_csr(csr_from_dense(int_sparse(rng, 16, 16, 0.3)), 4)
    Z = bcsr_from_csr(csr_from_dense(np.zeros((16, 16), np.float32)), 4)
    rank, pa, pb, flags = build_spgemm_schedule(A, A, Z)
    assert rank.shape == pa.shape == pb.shape == flags.shape == (0,)
    out = block_spgemm(A, A, Z)
    assert out.nnzb == 0 and out.blocks.shape == (0, 4, 4)
    assert np.abs(out.to_dense()).sum() == 0.0
    # empty A (no worklist hits): every mask block zero-fills
    full = bcsr_from_csr(csr_from_dense(np.ones((16, 16), np.float32)), 4)
    out = block_spgemm(Z, Z, full)
    assert out.nnzb == full.nnzb
    assert np.abs(np.asarray(out.blocks)).sum() == 0.0


def test_xla_and_pallas_executors_agree():
    rng = np.random.default_rng(2)
    A = bcsr_from_csr(csr_from_dense(int_sparse(rng, 24, 16, 0.3)), 8)
    B = bcsr_from_csr(csr_from_dense(int_sparse(rng, 16, 32, 0.3)), 8)
    M = bcsr_from_csr(csr_from_dense(int_sparse(rng, 24, 32, 0.5)), 8)
    xla = block_spgemm(A, B, M, backend="xla")
    pallas = block_spgemm(A, B, M, backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(xla.blocks),
                                  np.asarray(pallas.blocks))


@pytest.mark.parametrize("chunk", [1, 8, 64])
def test_chunked_pallas_replay_matches_xla(monkeypatch, chunk):
    """A worklist longer than one kernel call's chunk is replayed in
    several aliased calls; blocks a call does not visit must survive, so
    the result equals the one-call replay and the XLA executor."""
    rng = np.random.default_rng(3)
    A = bcsr_from_csr(csr_from_dense(int_sparse(rng, 32, 32, 0.4)), 4)
    M = bcsr_from_csr(csr_from_dense(int_sparse(rng, 32, 32, 0.6)), 4)
    want = block_spgemm(A, A, M, backend="xla")
    one_call = block_spgemm(A, A, M, backend="pallas", interpret=True)
    monkeypatch.setattr(ops, "SPGEMM_CHUNK", chunk)
    if chunk == 1:   # a rank with two entries cannot fit one entry
        with pytest.raises(ValueError, match="cannot fit in one chunk"):
            block_spgemm(A, A, M, backend="pallas", interpret=True)
        return
    got = block_spgemm(A, A, M, backend="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got.blocks),
                                  np.asarray(want.blocks))
    np.testing.assert_array_equal(np.asarray(got.blocks),
                                  np.asarray(one_call.blocks))


def test_chunk_schedule_splits_at_rank_boundaries():
    rng = np.random.default_rng(4)
    A = bcsr_from_csr(csr_from_dense(int_sparse(rng, 40, 40, 0.4)), 4)
    sched = build_spgemm_schedule(A, A, A)
    chunks = ops.chunk_schedule(sched, 16)
    assert chunks.shape[1:] == (4, 16) and chunks.dtype == np.int32
    live = chunks[:, 3, :] != 0
    # every entry lands exactly once, in order; padding has flags off
    for i, arr in enumerate(sched):
        np.testing.assert_array_equal(chunks[:, i, :][live], arr)
    for c in chunks:
        n = int((c[3] != 0).sum())
        assert c[3, n - 1] & 4, "a chunk must end on a rank's last visit"
        assert (c[0, n:] == c[0, n - 1]).all()
    assert len(chunks) >= -(-len(sched[0]) // 16)


def test_masked_matmul_rejects_untileable_blocks():
    """Compiled for the chip, blocks must tile by (8, 128); the check
    raises before Mosaic does, and interpret mode keeps small blocks."""
    a = jnp.ones((64, 64), jnp.float32)
    bi = bj = jnp.zeros(1, jnp.int32)
    with pytest.raises(ValueError, match="cannot tile"):
        ops.masked_matmul(a, a, bi, bj, bm=32, bn=32, bk=32,
                          interpret=False)
    with pytest.raises(ValueError, match="bn=8"):
        ops._check_tpu_tiling((256, 256), (256, 256), 128, 8, 128)
    ops._check_tpu_tiling((256, 256), (256, 256), 128, 128, 128)
    ops._check_tpu_tiling((8, 32), (32, 64), 8, 64, 32)   # full-dim blocks
    out = ops.masked_matmul(a, a, bi, bj, bm=32, bn=32, bk=32,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), 64.0)


def test_interpret_false_off_tpu_routes_to_xla():
    """Regression: ``interpret=False`` with the default backend used to be
    read as "pallas, compiled mode" — which crashes off-TPU (Mosaic cannot
    target the host platform).  An explicit non-interpret request off-TPU
    must fall through to the XLA executor and agree with it bitwise."""
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU routing test")
    rng = np.random.default_rng(21)
    A = bcsr_from_csr(csr_from_dense(int_sparse(rng, 24, 24, 0.3)), 8)
    M = bcsr_from_csr(csr_from_dense(int_sparse(rng, 24, 24, 0.5)), 8)
    got = block_spgemm(A, A, M, interpret=False)       # backend=None
    want = block_spgemm(A, A, M, backend="xla")
    np.testing.assert_array_equal(np.asarray(got.blocks),
                                  np.asarray(want.blocks))


def test_on_tpu_tracks_backend_changes(monkeypatch):
    """The executor choice must be re-derived per call: a module-global
    cache of the first backend probe silently ran compiled-mode kernels in
    the wrong mode after a backend switch."""
    assert ops.on_tpu() == (jax.default_backend() == "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.on_tpu() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.on_tpu() is False


# ---------------------------------------------------------------------------
# end-to-end tile route
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bs", [8, 16, 32])
@pytest.mark.parametrize("shape", [(64, 64, 64),     # divisible
                                   (50, 33, 70),     # non-divisible
                                   (8, 80, 24)])     # wide, tiny m
def test_tile_route_bitwise_vs_msa_and_oracle(bs, shape):
    m, k, n = shape
    rng = np.random.default_rng(bs * 1000 + m)
    A = int_sparse(rng, m, k, 0.2)
    A[m // 2, :] = 0.0                      # empty row
    B = int_sparse(rng, k, n, 0.2)
    M = (rng.random((m, n)) < 0.4).astype(np.float32)
    M[:, n // 2] = 0.0
    Ac, Bc, Mc = csr_from_dense(A), csr_from_dense(B), csr_from_dense(M)

    tile = masked_spgemm(Ac, Bc, Mc, algorithm="tile", tile_block=bs)
    msa = masked_spgemm(Ac, Bc, Mc, algorithm="msa")
    np.testing.assert_array_equal(np.asarray(tile.to_dense()),
                                  np.asarray(msa.to_dense()))
    np.testing.assert_array_equal(np.asarray(tile.present),
                                  np.asarray(msa.present))
    np.testing.assert_array_equal(np.asarray(tile.mask_cols),
                                  np.asarray(msa.mask_cols))

    want_vals, want_present = dense_oracle(A, B, M)
    np.testing.assert_array_equal(
        np.asarray(tile.to_dense()),
        np.where(np.asarray(want_present), np.asarray(want_vals), 0))


def test_tile_route_empty_mask():
    rng = np.random.default_rng(9)
    Ac = csr_from_dense(int_sparse(rng, 32, 32, 0.3))
    Mz = csr_from_dense(np.zeros((32, 32), np.float32))
    out = masked_spgemm(Ac, Ac, Mz, algorithm="tile", tile_block=8)
    assert int(out.nnz) == 0


def test_tile_route_structural_presence_under_cancellation():
    """present is structural (like the row kernels), not ``value != 0``:
    a mask position whose products cancel to 0.0 must stay present."""
    A = np.zeros((8, 8), np.float32)
    B = np.zeros((8, 8), np.float32)
    A[0, 0], A[0, 1] = 1.0, 1.0
    B[0, 0], B[1, 0] = 2.0, -2.0           # 1*2 + 1*(-2) == 0.0
    M = np.zeros((8, 8), np.float32)
    M[0, 0] = 1.0
    Ac, Bc, Mc = csr_from_dense(A), csr_from_dense(B), csr_from_dense(M)
    tile = masked_spgemm(Ac, Bc, Mc, algorithm="tile", tile_block=8)
    msa = masked_spgemm(Ac, Bc, Mc, algorithm="msa")
    np.testing.assert_array_equal(np.asarray(tile.present),
                                  np.asarray(msa.present))
    assert bool(np.asarray(tile.present)[0, 0])
    assert float(np.asarray(tile.vals)[0, 0]) == 0.0


def test_tile_route_explicit_stored_zero_is_structural():
    """An explicitly stored 0.0 entry (e.g. duplicates summing to zero in
    csr_from_coo) is structural to the row kernels; the tile route's
    stored-entry pattern replay must agree."""
    from repro.core.formats import CSR
    A = CSR(np.array([0, 2, 2]), np.array([0, 1]),
            np.array([0.0, 2.0], np.float32), (2, 2))
    B = csr_from_dense(np.eye(2, dtype=np.float32))
    M = csr_from_dense(np.ones((2, 2), np.float32))
    tile = masked_spgemm(A, B, M, algorithm="tile", tile_block=8)
    msa = masked_spgemm(A, B, M, algorithm="msa")
    np.testing.assert_array_equal(np.asarray(tile.present),
                                  np.asarray(msa.present))
    assert bool(np.asarray(tile.present)[0, 0])     # the stored 0.0


def test_xla_executor_chunking_matches_unchunked(monkeypatch):
    """Forcing a tiny chunk (a non-divisor of W) must not change the
    result: chunks are independent partial sums into the same output."""
    rng = np.random.default_rng(17)
    A = bcsr_from_csr(csr_from_dense(int_sparse(rng, 48, 48, 0.4)), 8)
    B = bcsr_from_csr(csr_from_dense(int_sparse(rng, 48, 48, 0.4)), 8)
    M = bcsr_from_csr(csr_from_dense(int_sparse(rng, 48, 48, 0.8)), 8)
    whole = block_spgemm(A, B, M, backend="xla")
    monkeypatch.setattr(ops, "_XLA_CHUNK_ELEMS", 8 * 8 * 7)
    chunked = block_spgemm(A, B, M, backend="xla")
    np.testing.assert_array_equal(np.asarray(whole.blocks),
                                  np.asarray(chunked.blocks))


def test_block_spgemm_from_csr_never_densifies(monkeypatch):
    """The Plan.tile_eligible route must not call to_dense() anywhere."""
    from repro.core import formats

    def boom(self):
        raise AssertionError("to_dense() on the tile path")

    monkeypatch.setattr(formats.CSR, "to_dense", boom)
    rng = np.random.default_rng(3)
    Ac = csr_from_dense(int_sparse(rng, 32, 32, 0.3))
    Mc = csr_from_dense((np.random.default_rng(4).random((32, 32)) < 0.5
                         ).astype(np.float32))
    out = block_spgemm_from_csr(Ac, Ac, Mc, block_size=8)
    assert out.nnzb > 0
    # the end-to-end driver route as well
    res = masked_spgemm(Ac, Ac, Mc, algorithm="tile", tile_block=8)
    assert int(res.nnz) >= 0


def test_planner_elected_tile_dispatches_and_matches():
    """A dense-block regime elects the tile route; auto output must equal
    the fixed msa row kernel bitwise."""
    clear_plan_cache()
    rng = np.random.default_rng(11)
    n = 256
    A = int_sparse(rng, n, n, 0.15)
    B = int_sparse(rng, n, n, 0.15)
    M = (rng.random((n, n)) < 0.5).astype(np.float32)
    Ac, Bc, Mc = csr_from_dense(A), csr_from_dense(B), csr_from_dense(M)
    p = plan(Ac, Bc, Mc)
    assert p.tile_eligible and p.tile_block in (8, 32, 128)
    auto = masked_spgemm(Ac, Bc, Mc, algorithm="auto")
    msa = masked_spgemm(Ac, Bc, Mc, algorithm="msa")
    np.testing.assert_array_equal(np.asarray(auto.to_dense()),
                                  np.asarray(msa.to_dense()))
    np.testing.assert_array_equal(np.asarray(auto.present),
                                  np.asarray(msa.present))


def test_two_phase_forced_tile_raises():
    """two_phase has no meaning on the tile route; a forced tile request
    must fail loudly instead of silently ignoring the flag."""
    rng = np.random.default_rng(23)
    Ac = csr_from_dense(int_sparse(rng, 16, 16, 0.3))
    Mc = csr_from_dense(np.ones((16, 16), np.float32))
    with pytest.raises(NotImplementedError):
        masked_spgemm(Ac, Ac, Mc, algorithm="tile", tile_block=8,
                      two_phase=True)


def test_two_phase_auto_elected_tile_falls_back_to_row_kernel():
    """When auto elects the tile route but the caller asked for two_phase,
    the driver must fall back to the plan's best row kernel — and still
    return the row kernels' exact result."""
    clear_plan_cache()
    rng = np.random.default_rng(24)
    n = 256
    A = int_sparse(rng, n, n, 0.15)
    B = int_sparse(rng, n, n, 0.15)
    M = (rng.random((n, n)) < 0.5).astype(np.float32)
    Ac, Bc, Mc = csr_from_dense(A), csr_from_dense(B), csr_from_dense(M)
    p = plan(Ac, Bc, Mc)
    if p.algorithm != "tile":
        pytest.skip("planner did not elect tile on this machine")
    out = masked_spgemm(Ac, Bc, Mc, algorithm="auto", two_phase=True)
    msa = masked_spgemm(Ac, Bc, Mc, algorithm="msa")
    np.testing.assert_array_equal(np.asarray(out.to_dense()),
                                  np.asarray(msa.to_dense()))
    np.testing.assert_array_equal(np.asarray(out.present),
                                  np.asarray(msa.present))


def test_tile_route_rejects_unsupported():
    from repro.core.semiring import MIN_PLUS
    rng = np.random.default_rng(13)
    Ac = csr_from_dense(int_sparse(rng, 16, 16, 0.3))
    Mc = csr_from_dense(np.ones((16, 16), np.float32))
    with pytest.raises(NotImplementedError):
        masked_spgemm(Ac, Ac, Mc, algorithm="tile", tile_block=8,
                      semiring=MIN_PLUS)
    with pytest.raises(NotImplementedError):
        masked_spgemm(Ac, Ac, Mc, algorithm="tile", tile_block=8,
                      complement=True)


def signed_with_stored_zeros(rng, n, density):
    """CSR with values in {-1, 0, 1, 2}, every 0 explicitly stored: the
    products of two such matrices cancel in places."""
    from repro.core.formats import csr_from_coo
    rows, cols = np.nonzero(rng.random((n, n)) < density)
    vals = rng.integers(-1, 3, len(rows)).astype(np.float32)
    return csr_from_coo(rows, cols, vals, (n, n), sum_dups=False)


@pytest.mark.parametrize("bs,chunk", [(8, None), (8, 8), (32, None)])
def test_tile_route_walks_values_and_pattern_once(monkeypatch, bs, chunk):
    """Stored zeros and cancelling pairs: the tile route's values and
    presence equal the row kernels' on the Pallas executor, whose one
    walk carries both planes (blocks stored ``(bs, bs)`` at bs 8,
    lane-dense at bs 32); its ``spgemm.chunk`` span counts the grid steps
    of that walk, the worklist rounded up to whole chunks."""
    from repro import obs
    from repro.core.masked_spgemm import _masked_spgemm_tile
    rng = np.random.default_rng(29)
    A, B, M = (signed_with_stored_zeros(rng, 40, d) for d in (0.2, 0.2, 0.4))
    assert (A.data == 0).any() and (B.data == 0).any()
    if chunk:
        monkeypatch.setattr(ops, "SPGEMM_CHUNK", chunk)
    with obs.tracing() as tr:
        tile = _masked_spgemm_tile(A, B, M, block_size=bs, backend="pallas",
                                   interpret=True)
    msa = masked_spgemm(A, B, M, algorithm="msa")
    present = np.asarray(msa.present)
    assert (present & (np.asarray(msa.vals) == 0)).any(), "no cancellation"
    np.testing.assert_array_equal(np.asarray(tile.present), present)
    np.testing.assert_array_equal(np.asarray(tile.vals),
                                  np.asarray(msa.vals))
    (sp,) = [r["attrs"] for r in tr.sink.spans()
             if r["name"] == "spgemm.chunk"]
    schedule = build_spgemm_schedule(*(bcsr_from_csr(x, bs)
                                       for x in (A, B, M)))
    length = min(ops.SPGEMM_CHUNK, len(schedule[0]))
    n_chunks = len(ops.chunk_schedule(schedule, length))
    assert sp == {"chunks": n_chunks, "planes": 2,
                  "steps": n_chunks * length}
    if chunk is None:
        assert sp["steps"] == len(schedule[0])
    else:
        assert n_chunks > 1 and sp["steps"] >= len(schedule[0])
