"""Element-level accumulators (paper Sec. 5) vs the dense oracle.

Covers: MSA / Hash / MCA / Heap / HeapDot / Inner, arbitrary semirings,
complemented masks (MSA, Heap), 1P/2P, mask-aligned stability.
"""
import re

import jax
import numpy as np
import pytest
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st, HealthCheck
except ImportError:  # container has no hypothesis; deterministic fallback
    from _hypothesis_shim import given, settings, strategies as st, HealthCheck

from repro.core import accumulators as acc
from repro.core.formats import csr_from_dense, padded_from_csr
from repro.core.masked_spgemm import masked_spgemm, dense_oracle, ALGORITHMS
from repro.core.semiring import (PLUS_TIMES, MIN_PLUS, OR_AND, PLUS_SECOND,
                                 REGISTRY)

ALL_ALGOS = list(ALGORITHMS)


def make_problem(seed, m, k, n, da, db, dm):
    rng = np.random.default_rng(seed)
    A = (rng.random((m, k)) < da) * rng.uniform(0.5, 1.5, (m, k))
    B = (rng.random((k, n)) < db) * rng.uniform(0.5, 1.5, (k, n))
    M = (rng.random((m, n)) < dm).astype(np.float32)
    return A.astype(np.float32), B.astype(np.float32), M


def check(algorithm, A, B, M, semiring=PLUS_TIMES, complement=False,
          two_phase=False, **kw):
    Ac, Bc, Mc = csr_from_dense(A), csr_from_dense(B), csr_from_dense(M)
    want_vals, want_present = dense_oracle(A, B, M, semiring=semiring,
                                           complement=complement)
    out = masked_spgemm(Ac, Bc, Mc, algorithm=algorithm, semiring=semiring,
                        complement=complement, two_phase=two_phase, **kw)
    if complement:
        vals, present = out
        got_present = np.asarray(present)
        got_vals = np.asarray(vals)
    else:
        m, n = out.shape
        got_present = np.zeros((m, n), bool)
        got_vals = np.zeros((m, n), np.asarray(out.vals).dtype)
        rows, slots = np.nonzero(np.asarray(out.present))
        cols = np.asarray(out.mask_cols)[rows, slots]
        got_present[rows, cols] = True
        got_vals[rows, cols] = np.asarray(out.vals)[rows, slots]
    want_present = np.asarray(want_present)
    np.testing.assert_array_equal(got_present, want_present)
    np.testing.assert_allclose(got_vals[want_present],
                               np.asarray(want_vals)[want_present],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("algorithm", ALL_ALGOS)
@pytest.mark.parametrize("density", [(0.1, 0.1, 0.1), (0.4, 0.3, 0.05),
                                     (0.05, 0.05, 0.6), (0.3, 0.3, 0.3)])
def test_matches_oracle(algorithm, density):
    da, db, dm = density
    A, B, M = make_problem(1, 17, 23, 19, da, db, dm)
    check(algorithm, A, B, M)


@pytest.mark.parametrize("algorithm", ALL_ALGOS)
def test_empty_mask(algorithm):
    A, B, M = make_problem(2, 8, 8, 8, 0.3, 0.3, 0.2)
    M[:] = 0.0
    check(algorithm, A, B, M)


@pytest.mark.parametrize("algorithm", ALL_ALGOS)
def test_empty_inputs(algorithm):
    A, B, M = make_problem(3, 8, 8, 8, 0.3, 0.3, 0.3)
    A[:] = 0.0
    check(algorithm, A, B, M)


@pytest.mark.parametrize("algorithm", ALL_ALGOS)
def test_full_mask(algorithm):
    A, B, M = make_problem(4, 9, 7, 11, 0.3, 0.4, 1.1)
    assert (M == 1).all()
    check(algorithm, A, B, M)


@pytest.mark.parametrize("algorithm", ["msa", "heap"])
def test_complemented_mask(algorithm):
    A, B, M = make_problem(5, 13, 11, 12, 0.3, 0.3, 0.4)
    check(algorithm, A, B, M, complement=True)


def test_mca_complement_raises():
    A, B, M = make_problem(6, 4, 4, 4, 0.5, 0.5, 0.5)
    with pytest.raises(NotImplementedError):
        check("mca", A, B, M, complement=True)


@pytest.mark.parametrize("algorithm", ["msa", "hash", "mca", "inner"])
@pytest.mark.parametrize("semiring", [MIN_PLUS, OR_AND, PLUS_SECOND],
                         ids=lambda s: s.name)
def test_semirings(algorithm, semiring):
    A, B, M = make_problem(7, 11, 13, 9, 0.3, 0.3, 0.4)
    if semiring is OR_AND:
        A = (A > 0).astype(np.float32)
        B = (B > 0).astype(np.float32)
    check(algorithm, A, B, M, semiring=semiring)


@pytest.mark.parametrize("algorithm", ["heap", "heapdot"])
@pytest.mark.parametrize("semiring", [MIN_PLUS, PLUS_SECOND],
                         ids=lambda s: s.name)
def test_heap_semirings(algorithm, semiring):
    A, B, M = make_problem(8, 11, 13, 9, 0.3, 0.3, 0.4)
    check(algorithm, A, B, M, semiring=semiring)


@pytest.mark.parametrize("algorithm", ALL_ALGOS)
def test_two_phase_equals_one_phase(algorithm):
    A, B, M = make_problem(9, 10, 12, 14, 0.25, 0.25, 0.3)
    check(algorithm, A, B, M, two_phase=True)


def test_output_is_mask_aligned_and_sorted():
    A, B, M = make_problem(10, 12, 10, 15, 0.3, 0.3, 0.4)
    out = masked_spgemm(csr_from_dense(A), csr_from_dense(B),
                        csr_from_dense(M), algorithm="msa")
    cols = np.asarray(out.mask_cols)
    n = out.shape[1]
    for i in range(cols.shape[0]):
        real = cols[i][cols[i] < n]
        assert (np.diff(real) > 0).all()  # sorted, unique (stable gather)


def test_symbolic_phase_counts():
    from repro.core.masked_spgemm import symbolic_phase
    A, B, M = make_problem(11, 14, 9, 13, 0.3, 0.3, 0.35)
    Ap = padded_from_csr(csr_from_dense(A))
    Bp = padded_from_csr(csr_from_dense(B))
    Mp = padded_from_csr(csr_from_dense(M))
    counts = np.asarray(symbolic_phase(Ap, Mp, Bp, shape=(14, 13), kdim=9))
    _, present = dense_oracle(A, B, M)
    np.testing.assert_array_equal(counts, np.asarray(present).sum(axis=1))


def test_result_to_csr_roundtrip():
    A, B, M = make_problem(12, 9, 9, 9, 0.35, 0.35, 0.4)
    out = masked_spgemm(csr_from_dense(A), csr_from_dense(B),
                        csr_from_dense(M), algorithm="hash")
    got = out.to_csr().to_dense()
    want = np.asarray(out.to_dense())
    np.testing.assert_allclose(got, want, rtol=1e-6)


@settings(max_examples=25, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    seed=st.integers(0, 2**31 - 1),
    m=st.integers(1, 12), k=st.integers(1, 12), n=st.integers(1, 12),
    da=st.floats(0.0, 0.8), db=st.floats(0.0, 0.8), dm=st.floats(0.0, 1.0),
    algorithm=st.sampled_from(ALL_ALGOS),
)
def test_property_matches_oracle(seed, m, k, n, da, db, dm, algorithm):
    A, B, M = make_problem(seed, m, k, n, da, db, dm)
    check(algorithm, A, B, M)


@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(seed=st.integers(0, 2**31 - 1),
       algorithm=st.sampled_from(["msa", "heap"]))
def test_property_complement(seed, algorithm):
    A, B, M = make_problem(seed, 9, 8, 10, 0.3, 0.3, 0.4)
    check(algorithm, A, B, M, complement=True)


# ---------------------------------------------------------------------------
# Inner: the compare intersection against a binary search
# ---------------------------------------------------------------------------

#: (wa, wbt): the uniform scale-16 triangle count's widths, and a B^T row
#: far wider than its A row (a hub column of a skewed graph)
INNER_WIDTHS = [(29, 58), (16, 1400)]


def _inner_operands(seed, wa, wbt, m=7, pm=6, n=11, integral=False):
    """Padded operands of one inner product with every edge case: padded
    and empty rows, A columns at or past kdim inside a row's length, mask
    pads, explicitly stored zeros (both signs), non-zero garbage in the
    value pads, and real-looking column ids past a B^T row's length.
    ``integral`` draws whole-number values, whose sums are exact."""
    rng = np.random.default_rng(seed)
    kdim = wbt + 9

    def rows(count, width, bound, empty):
        cols = np.full((count, width), bound, np.int32)
        lens = rng.integers(0, width + 1, count).astype(np.int32)
        lens[empty] = 0
        lens[-1] = width                              # one full row
        for r in range(count):
            cols[r, :lens[r]] = np.sort(
                rng.choice(bound, lens[r], replace=False))
        if integral:
            vals = rng.integers(-3, 4, (count, width)).astype(np.float32)
        else:
            vals = rng.uniform(-2.0, 2.0, (count, width)).astype(np.float32)
        vals[rng.random((count, width)) < 0.15] = 0.0
        vals[rng.random((count, width)) < 0.1] = -0.0
        return cols, vals, lens

    # A's columns are drawn from B^T's own, so most slots intersect
    bt_cols, bt_vals, bt_lens = rows(n, wbt, kdim, empty=[1])
    a_cols, a_vals, a_lens = rows(m, wa, kdim, empty=[0])
    a_lens[2] = max(a_lens[2], 4)
    for r in range(m):
        pool = np.union1d(bt_cols[rng.integers(0, n, 3)],
                          rng.choice(kdim, wa, replace=False))
        pool = pool[pool < kdim]
        a_cols[r, :a_lens[r]] = np.sort(rng.choice(pool, a_lens[r],
                                                   replace=False))
    a_cols[-1, -2:] = [kdim, kdim + 3]           # past kdim, inside the row
    # B^T row 2 holds the first half of A row 2's columns; the second half
    # sits, still sorted, in the slots past its length
    a2 = a_cols[2, :a_lens[2]]
    bt_cols[2] = kdim
    bt_cols[2, :len(a2)] = a2
    bt_lens[2] = len(a2) // 2
    m_cols = np.full((m, pm), n, np.int32)                   # mask pads
    for r in range(1, m):
        k = rng.integers(1, pm + 1)
        m_cols[r, :k] = np.sort(rng.choice(n, k, replace=False))
    m_cols[2] = n
    m_cols[2, 0] = 2
    return (jnp.asarray(m_cols), jnp.asarray(a_cols), jnp.asarray(a_vals),
            jnp.asarray(a_lens), jnp.asarray(bt_cols),
            jnp.asarray(bt_vals), jnp.asarray(bt_lens)), n, kdim


def _inner_row_by_search(m_cols, a_cols, a_vals, a_len,
                         Bt_cols, Bt_vals, Bt_lens, n, kdim, sr):
    """The inner kernel intersecting by a binary search of each A column
    in the B^T row, then gathering the column, value and validity found."""
    a_valid = jnp.arange(a_cols.shape[0]) < a_len

    def one_dot(j):
        bcols, bvals = Bt_cols[j], Bt_vals[j]
        bvalid = jnp.arange(bcols.shape[0]) < Bt_lens[j]
        idx = jnp.minimum(jnp.searchsorted(bcols, a_cols), bcols.shape[0] - 1)
        hit = (bcols[idx] == a_cols) & a_valid & (a_cols < kdim) & bvalid[idx]
        contrib = jnp.where(hit, sr.mul(a_vals, bvals[idx]), sr.zero)
        red = jax.lax.reduce(contrib, jnp.asarray(sr.zero, contrib.dtype),
                             sr.add, (0,))
        return red, jnp.any(hit)

    vals, present = jax.vmap(one_dot)(jnp.minimum(m_cols, n - 1))
    present = present & (m_cols < n)
    return jnp.where(present, vals, sr.zero), present


@pytest.mark.parametrize("integral", [True, False], ids=["whole", "float"])
@pytest.mark.parametrize("widths", INNER_WIDTHS, ids=str)
@pytest.mark.parametrize("semiring", list(REGISTRY.values()),
                         ids=lambda s: s.name)
def test_inner_compare_all_bitwise_equals_search(widths, semiring,
                                                 integral):
    """``present`` is bitwise the search's, and so is every value whose
    reduction is exact in any order: whole numbers, and min/max semirings.
    A float plus sum is the same terms folded by the same ``lax.reduce``,
    whose order XLA picks per program, so its last bits may differ."""
    wa, wbt = widths
    ops, n, kdim = _inner_operands(31, wa, wbt, integral=integral)
    mc, ac, av, al, btc, btv, btl = ops

    def run(kernel):
        f = jax.jit(jax.vmap(lambda mc, ac, av, al: kernel(
            mc, ac, av, al, btc, btv, btl, n, kdim, semiring)))
        vals, present = f(mc, ac, av, al)
        return np.asarray(vals), np.asarray(present)

    cmp_vals, cmp_present = run(acc.inner_row)
    srch_vals, srch_present = run(_inner_row_by_search)
    assert cmp_present.any() and not cmp_present.all()
    np.testing.assert_array_equal(cmp_present, srch_present)
    if integral or semiring.add is not jnp.add:
        np.testing.assert_array_equal(cmp_vals.view(np.uint32),
                                      srch_vals.view(np.uint32))
    else:
        np.testing.assert_allclose(cmp_vals, srch_vals, rtol=1e-6,
                                   atol=1e-6)


@pytest.mark.parametrize("widths", INNER_WIDTHS, ids=str)
def test_inner_intersect_returns_the_matched_bits(widths):
    """Each A slot's matched B^T value comes back bit for bit (signed
    zeros included) and a slot past either row's length matches nothing."""
    wa, wbt = widths
    (mc, ac, av, al, btc, btv, btl), n, kdim = _inner_operands(
        7, wa, wbt)
    a_ok = (jnp.arange(wa) < al[:, None]) & (ac < kdim)
    bt_cols = jnp.where(jnp.arange(wbt) < btl[:, None], btc, kdim)
    bits, hit = jax.vmap(acc._intersect, in_axes=(0, 0, None, None))(
        ac, a_ok, bt_cols[2], jax.lax.bitcast_convert_type(btv[2],
                                                         jnp.uint32))
    want = np.zeros((ac.shape[0], wa), np.uint32)
    want_hit = np.zeros((ac.shape[0], wa), bool)
    row = dict(zip(np.asarray(btc[2, :btl[2]]).tolist(),
                   np.asarray(btv[2, :btl[2]]).view(np.uint32).tolist()))
    for i in range(ac.shape[0]):
        for s in range(int(al[i])):
            c = int(ac[i, s])
            if c < kdim and c in row:
                want[i, s], want_hit[i, s] = row[c], True
    assert want_hit.any()
    np.testing.assert_array_equal(np.asarray(hit), want_hit)
    np.testing.assert_array_equal(np.asarray(bits), want)


def _inner_program_hlo(wa, wbt, pm, m=64, n=2048):
    from repro.core.formats import PaddedCSR
    from repro.core.masked_spgemm import _masked_spgemm_padded

    def padded(rows, width, ncols):
        return PaddedCSR(jax.ShapeDtypeStruct((rows, width), jnp.int32),
                         jax.ShapeDtypeStruct((rows, width), jnp.float32),
                         jax.ShapeDtypeStruct((rows,), jnp.int32),
                         (rows, ncols))

    return _masked_spgemm_padded.lower(
        padded(m, pm, n), padded(m, wa, n), padded(n, wbt, n),
        algorithm="inner", sr=PLUS_TIMES, complement=False, n_inspect=None,
        shape=(m, n), kdim=n).compile().as_text()


@pytest.mark.parametrize("widths", [(29, 58, 29), (75, 1318, 75)], ids=str)
def test_inner_program_structure(widths):
    """At the uniform scale-16 triangle count's widths, and at the R-MAT
    scale-12 one's (a B^T row far wider than its A row), the compiled
    inner program holds no loop and no element gather (every slice size
    1): only whole-row gathers."""
    text = _inner_program_hlo(*widths)
    slices = re.findall(r"\bgather\(.*slice_sizes=\{([0-9,]+)\}", text)
    scalar = [s for s in slices if set(s.split(",")) == {"1"}]
    assert slices
    assert re.search(r"\bwhile\(", text) is None
    assert not scalar, slices


@pytest.mark.parametrize("algorithm", ["inner", "mca"])
def test_inner_span_records_intersect_mode(algorithm):
    """The ``spgemm.row`` span of an inner product records the compare
    intersection as ``intersect``; other kernels carry no such attribute."""
    from repro import obs
    A, B, M = make_problem(13, 12, 10, 15, 0.3, 0.3, 0.4)
    Ac, Bc, Mc = csr_from_dense(A), csr_from_dense(B), csr_from_dense(M)
    with obs.tracing() as tr:
        masked_spgemm(Ac, Bc, Mc, algorithm=algorithm)
    (rec,) = [r for r in tr.sink.spans() if r["name"] == "spgemm.row"]
    if algorithm == "inner":
        assert rec["attrs"]["intersect"] == "compare_all"
    else:
        assert "intersect" not in rec["attrs"]
