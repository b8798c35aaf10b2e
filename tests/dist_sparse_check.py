"""Subprocess body for the sparse BCSR ring-SUMMA tests: 8 fake host devices.

Run as:  python tests/dist_sparse_check.py
(invoked by tests/test_distributed.py).  Value matrices use small random
integers so every summation order is exact in float32 — assertions are
bitwise (array_equal), matching the single-device driver exactly.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import formats  # noqa: E402
from repro.core.formats import CSR, BCSR, PaddedCSR, csr_from_dense  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    distributed_masked_spgemm, ring_sparse_masked_spgemm)
from repro.core.masked_spgemm import dense_oracle, masked_spgemm  # noqa: E402
from repro.core.planner import collect_stats, decide_distributed  # noqa: E402
from repro.core.semiring import MIN_PLUS  # noqa: E402

rng = np.random.default_rng(0)


def int_sparse(m, n, density):
    return ((rng.random((m, n)) < density)
            * rng.integers(1, 5, (m, n))).astype(np.float32)


def mesh_of(p):
    return Mesh(np.array(jax.devices()[:p]), ("data",))


def check_bitwise(out, A, B, M):
    """out must match the single-device row kernel AND the dense oracle."""
    Ac, Bc, Mc = csr_from_dense(A), csr_from_dense(B), csr_from_dense(M)
    ref = masked_spgemm(Ac, Bc, Mc, algorithm="msa")
    np.testing.assert_array_equal(np.asarray(out.to_dense()),
                                  np.asarray(ref.to_dense()))
    np.testing.assert_array_equal(np.asarray(out.present),
                                  np.asarray(ref.present))
    np.testing.assert_array_equal(np.asarray(out.mask_cols),
                                  np.asarray(ref.mask_cols))
    want_vals, want_present = dense_oracle(A, B, M)
    np.testing.assert_array_equal(
        np.asarray(out.to_dense()),
        np.where(np.asarray(want_present), np.asarray(want_vals), 0))


def ring_vs_oracle_over_meshes():
    """Bitwise agreement at every mesh size, incl. non-divisible shapes."""
    shapes = [(64, 64, 64),     # divisible
              (50, 33, 70),     # non-divisible everything
              (8, 80, 24)]      # wide, tiny m
    for p in (1, 2, 4, 8):
        mesh = mesh_of(p)
        for m, k, n in shapes:
            A = int_sparse(m, k, 0.2)
            A[m // 2, :] = 0.0                     # empty row
            B = int_sparse(k, n, 0.2)
            M = (rng.random((m, n)) < 0.4).astype(np.float32)
            M[:, n // 2] = 0.0
            out = ring_sparse_masked_spgemm(
                csr_from_dense(A), csr_from_dense(B), csr_from_dense(M),
                mesh, block_size=8)
            check_bitwise(out, A, B, M)
    print("ring_vs_oracle OK")


def ring_pallas_chunked():
    """The ring's Pallas stages (interpret mode) replay chunked worklists:
    with chunks far shorter than a stage's worklist, and stages padded
    with never-launched chunks, the result stays bitwise."""
    from repro.core.distributed import clear_ring_prep_cache
    from repro.kernels.masked_matmul import ops
    full = ops.SPGEMM_CHUNK
    A = int_sparse(48, 40, 0.3)
    B = int_sparse(40, 56, 0.3)
    M = (rng.random((48, 56)) < 0.5).astype(np.float32)
    try:
        for chunk in (full, 16):
            ops.SPGEMM_CHUNK = chunk
            clear_ring_prep_cache()
            for p in (2, 4):
                out = ring_sparse_masked_spgemm(
                    csr_from_dense(A), csr_from_dense(B), csr_from_dense(M),
                    mesh_of(p), block_size=8, backend="pallas",
                    interpret=True)
                check_bitwise(out, A, B, M)
    finally:
        ops.SPGEMM_CHUNK = full
        clear_ring_prep_cache()
    print("ring_pallas_chunked OK")


def ring_empty_mask_and_empty_slabs():
    mesh = mesh_of(8)
    # empty mask: defined degenerate, no kernel work
    A = int_sparse(32, 32, 0.3)
    Z = np.zeros((32, 32), np.float32)
    out = ring_sparse_masked_spgemm(csr_from_dense(A), csr_from_dense(A),
                                    csr_from_dense(Z), mesh, block_size=8)
    assert int(out.nnz) == 0
    # empty K-slabs: k = 24, bs = 8 -> 3 occupied B block-rows over an
    # 8-stage ring; 5+ slabs are structurally empty and must contribute 0
    m, k, n = 40, 24, 40
    A = int_sparse(m, k, 0.3)
    B = int_sparse(k, n, 0.3)
    M = (rng.random((m, n)) < 0.5).astype(np.float32)
    out = ring_sparse_masked_spgemm(csr_from_dense(A), csr_from_dense(B),
                                    csr_from_dense(M), mesh, block_size=8)
    check_bitwise(out, A, B, M)
    # B entirely empty: every slab is empty
    Bz = np.zeros((k, n), np.float32)
    out = ring_sparse_masked_spgemm(csr_from_dense(A), csr_from_dense(Bz),
                                    csr_from_dense(M), mesh, block_size=8)
    check_bitwise(out, A, Bz, M)
    print("ring_edges OK")


def ring_never_densifies():
    """No dense (k, n)/(m, n) intermediate on the sparse ring path: any
    to_dense() on any format during the call is a failure."""
    mesh = mesh_of(4)
    A = int_sparse(48, 48, 0.25)
    B = int_sparse(48, 48, 0.25)
    M = (rng.random((48, 48)) < 0.5).astype(np.float32)
    Ac, Bc, Mc = csr_from_dense(A), csr_from_dense(B), csr_from_dense(M)

    def boom(self):
        raise AssertionError("to_dense() on the sparse ring path")

    saved = [(cls, cls.to_dense) for cls in (CSR, BCSR, PaddedCSR)]
    try:
        for cls, _ in saved:
            cls.to_dense = boom
        out = ring_sparse_masked_spgemm(Ac, Bc, Mc, mesh, block_size=8)
        assert int(out.nnz) > 0
    finally:
        for cls, fn in saved:
            cls.to_dense = fn
    check_bitwise(out, A, B, M)
    print("ring_no_densify OK")


def entry_point_routes_and_matches():
    """distributed_masked_spgemm: forced + auto routes, all bitwise."""
    mesh = mesh_of(8)
    m, k, n = 100, 60, 88                      # non-divisible by 8 rows
    A = int_sparse(m, k, 0.15)
    B = int_sparse(k, n, 0.15)
    M = (rng.random((m, n)) < 0.4).astype(np.float32)
    Ac, Bc, Mc = csr_from_dense(A), csr_from_dense(B), csr_from_dense(M)
    for algorithm in ("row", "ring", "auto"):
        out = distributed_masked_spgemm(Ac, Bc, Mc, mesh,
                                        algorithm=algorithm)
        check_bitwise(out, A, B, M)
    # auto consults the distributed cost model and picks a listed route
    dplan = decide_distributed(collect_stats(Ac, Bc, Mc), 8)
    assert dplan.route in ("row", "ring"), dplan
    assert dict(dplan.costs)[dplan.route] == dplan.costs[0][1]
    # row route with the inner row kernel (exercises the B^T contract)
    out = distributed_masked_spgemm(Ac, Bc, Mc, mesh, algorithm="row",
                                    row_algorithm="inner")
    check_bitwise(out, A, B, M)
    # unsupported products: ring refuses, row handles the semiring
    try:
        distributed_masked_spgemm(Ac, Bc, Mc, mesh, algorithm="ring",
                                  semiring=MIN_PLUS)
        raise SystemExit("ring accepted a non-plus_times semiring")
    except NotImplementedError:
        pass
    out = distributed_masked_spgemm(Ac, Bc, Mc, mesh, algorithm="auto",
                                    semiring=MIN_PLUS)
    ref = masked_spgemm(Ac, Bc, Mc, algorithm="msa", semiring=MIN_PLUS)
    np.testing.assert_array_equal(np.asarray(out.to_dense()),
                                  np.asarray(ref.to_dense()))
    print("entry_point OK")


def main():
    assert jax.device_count() == 8, jax.devices()
    ring_vs_oracle_over_meshes()
    ring_pallas_chunked()
    ring_empty_mask_and_empty_slabs()
    ring_never_densifies()
    entry_point_routes_and_matches()


if __name__ == "__main__":
    main()
    print("DIST_SPARSE_ALL_OK")
