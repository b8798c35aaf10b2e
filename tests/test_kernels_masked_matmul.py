"""Pallas masked_matmul / block_spgemm kernels vs pure-jnp oracles.

All runs use interpret=True (CPU container; TPU is the target). Shapes and
dtypes are swept per the deliverable-c requirement.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.formats import bcsr_from_dense
from repro.kernels.masked_matmul.kernel import masked_matmul_kernel
from repro.kernels.masked_matmul.ops import (
    block_spgemm, build_spgemm_schedule, masked_matmul)
from repro.kernels.masked_matmul.ref import masked_matmul_ref, block_spgemm_ref


def random_block_mask(rng, mb, nb, density):
    ok = rng.random((mb, nb)) < density
    if not ok.any():
        ok[0, 0] = True
    bi, bj = np.nonzero(ok)
    return bi.astype(np.int32), bj.astype(np.int32)


@pytest.mark.parametrize("shape", [(16, 16, 16), (32, 48, 64), (64, 32, 16),
                                   (128, 128, 128)])
@pytest.mark.parametrize("blocks", [(8, 8, 8), (16, 16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_matmul_sweep(shape, blocks, dtype):
    M, K, N = shape
    bm, bk, bn = blocks
    if M % bm or K % bk or N % bn:
        pytest.skip("not divisible")
    rng = np.random.default_rng(42)
    a = jnp.asarray(rng.standard_normal((M, K)), dtype)
    b = jnp.asarray(rng.standard_normal((K, N)), dtype)
    bi, bj = random_block_mask(rng, M // bm, N // bn, 0.4)
    got = masked_matmul_kernel(a, b, jnp.asarray(bi), jnp.asarray(bj),
                               bm=bm, bn=bn, bk=bk, interpret=True)
    want = masked_matmul_ref(a, b, bi, bj, bm=bm, bn=bn)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_masked_matmul_jit_wrapper():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
    bi, bj = random_block_mask(rng, 4, 4, 0.5)
    got = masked_matmul(a, b, jnp.asarray(bi), jnp.asarray(bj),
                        bm=8, bn=8, bk=8, interpret=True)
    want = masked_matmul_ref(a, b, bi, bj, bm=8, bn=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("bs", [4, 8])
@pytest.mark.parametrize("densities", [(0.3, 0.3, 0.3), (0.1, 0.5, 0.2),
                                       (0.6, 0.1, 0.9)])
def test_block_spgemm_sweep(bs, densities):
    da, db, dm = densities
    rng = np.random.default_rng(7)
    M, K, N = 4 * bs, 6 * bs, 5 * bs

    def sp(m, n, d):
        x = (rng.random((m, n)) < d) * rng.standard_normal((m, n))
        return x.astype(np.float32)

    A, B, Mk = sp(M, K, da), sp(K, N, db), sp(M, N, dm)
    Ab, Bb, Mb = (bcsr_from_dense(A, bs), bcsr_from_dense(B, bs),
                  bcsr_from_dense((Mk != 0).astype(np.float32), bs))
    if Mb.nnzb == 0:
        pytest.skip("empty mask")
    got = block_spgemm(Ab, Bb, Mb, interpret=True)
    bi = np.repeat(np.arange(Mb.block_rows), np.diff(Mb.indptr))
    want = block_spgemm_ref(A, B, bi, Mb.indices, bs=bs)
    np.testing.assert_allclose(np.asarray(got.blocks), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # output structure == mask structure (1P allocation, paper Sec. 6)
    np.testing.assert_array_equal(got.indptr, Mb.indptr)
    np.testing.assert_array_equal(got.indices, Mb.indices)


def test_block_spgemm_empty_contribution():
    """Mask blocks with no structural product must come out exactly zero."""
    bs = 4
    A = np.zeros((8, 8), np.float32)
    A[0, 0] = 1.0                        # only block (0, 0) of A
    B = np.zeros((8, 8), np.float32)
    B[0, 0] = 2.0                        # only block (0, 0) of B
    Mk = np.ones((8, 8), np.float32)     # mask allows everything
    got = block_spgemm(bcsr_from_dense(A, bs), bcsr_from_dense(B, bs),
                       bcsr_from_dense(Mk, bs), interpret=True)
    dense = got.to_dense()
    assert dense[0, 0] == 2.0
    assert np.abs(dense).sum() == 2.0


def test_schedule_is_sorted_and_flagged():
    rng = np.random.default_rng(3)
    A = (rng.random((16, 16)) < 0.4).astype(np.float32)
    B = (rng.random((16, 16)) < 0.4).astype(np.float32)
    Mk = (rng.random((16, 16)) < 0.5).astype(np.float32)
    Ab, Bb, Mb = (bcsr_from_dense(A, 4), bcsr_from_dense(B, 4),
                  bcsr_from_dense(Mk, 4))
    rank, pa, pb, flags = build_spgemm_schedule(Ab, Bb, Mb)
    assert (np.diff(rank) >= 0).all()
    assert set(rank.tolist()) == set(range(Mb.nnzb))
    for r in range(Mb.nnzb):
        fs = flags[rank == r]
        assert fs[0] & 1 and fs[-1] & 4   # first/last flags per rank


@pytest.mark.parametrize("bs", [8, 32])
@pytest.mark.parametrize("accumulate", [False, True])
def test_stacked_walk_equals_one_walk_per_plane(bs, accumulate):
    """One walk over operands stacked ``(nnzb, 2, *block)`` equals two
    walks over each plane alone, bitwise, and the products the worklist
    names: bs 8 in the ``(bs, bs)`` layout, bs 32 lane-dense, with
    zero-fill ranks and chunk tails whose flags are all off."""
    import functools
    import jax
    from repro.kernels.masked_matmul.kernel import (block_layout,
                                                    pack_blocks,
                                                    unpack_blocks)
    from repro.kernels.masked_matmul.ops import chunk_schedule, replay_chunks
    rng = np.random.default_rng(bs + accumulate)
    rows, lanes = block_layout(bs)
    assert (rows == bs) == (bs == 8)

    def structure(density):
        occupied = rng.random((6, 6)) < density
        return bcsr_from_dense(np.kron(occupied, np.ones((bs, bs),
                                                         np.float32)), bs)

    Ab, Bb, Mb = structure(0.4), structure(0.4), structure(0.6)
    schedule = build_spgemm_schedule(Ab, Bb, Mb)
    rank, pa, pb, flags = schedule
    real = (flags >> 1) & 1 == 1
    assert (~real).any(), "no zero-fill rank"
    chunks = chunk_schedule(schedule, 16)
    assert len(chunks) > 1 and (chunks[:, 3, :] == 0).any(), "no tail"

    def ints(n):
        return rng.integers(-3, 4, (n, 2, rows, lanes)).astype(np.float32)

    a, b, out = ints(Ab.nnzb), ints(Bb.nnzb), ints(Mb.nnzb)
    run = jax.jit(functools.partial(replay_chunks, bs=bs, interpret=True,
                                    accumulate=accumulate))
    both = np.asarray(run(out, a, b, jnp.asarray(chunks)))
    assert both.shape == out.shape
    for q in range(2):
        one = np.asarray(run(out[:, q], a[:, q], b[:, q],
                             jnp.asarray(chunks)))
        np.testing.assert_array_equal(both[:, q], one)
        # small integers: every summation order is exact in float32
        want = (np.array(unpack_blocks(out[:, q], bs)) if accumulate
                else np.zeros((Mb.nnzb, bs, bs), np.float32))
        ua = np.asarray(unpack_blocks(a[:, q], bs))
        ub = np.asarray(unpack_blocks(b[:, q], bs))
        for r, i, j in zip(rank[real], pa[real], pb[real]):
            want[r] += ua[i] @ ub[j]
        np.testing.assert_array_equal(
            one, np.asarray(pack_blocks(jnp.asarray(want), rows)))
