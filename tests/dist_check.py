"""Subprocess body for distributed tests: 8 fake host devices.

Run as:  XLA_FLAGS=... python tests/dist_check.py
(invoked by tests/test_distributed.py; asserts shard_map results equal the
single-logical-device reference).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.formats import (  # noqa: E402
    csr_from_dense, padded_from_csr)
from repro.core.distributed import (  # noqa: E402
    ring_masked_matmul, row_parallel_masked_spgemm, pad_rows_to)
from repro.core.masked_spgemm import dense_oracle  # noqa: E402


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)

    # ---- row-parallel element-level masked spgemm -------------------------
    m, k, n = 64, 48, 56
    A = ((rng.random((m, k)) < 0.2) * rng.uniform(0.5, 1.5, (m, k))
         ).astype(np.float32)
    B = ((rng.random((k, n)) < 0.2) * rng.uniform(0.5, 1.5, (k, n))
         ).astype(np.float32)
    M = (rng.random((m, n)) < 0.3).astype(np.float32)
    Ap = padded_from_csr(csr_from_dense(A))
    Bp = padded_from_csr(csr_from_dense(B))
    Mp = padded_from_csr(csr_from_dense(M))
    Ap, Mp = pad_rows_to(4, Ap, Mp)

    vals, present = row_parallel_masked_spgemm(Ap, Bp, Mp, mesh,
                                               algorithm="msa")
    want_vals, want_present = dense_oracle(A, B, M)
    got = np.zeros((Mp.shape[0], n + 1), np.float32)
    rows = np.broadcast_to(np.arange(Mp.shape[0])[:, None],
                           np.asarray(Mp.cols).shape)
    cols = np.where(np.asarray(present), np.asarray(Mp.cols), n)
    got[rows.ravel(), cols.ravel()] = np.where(
        np.asarray(present), np.asarray(vals), 0).ravel()
    want = np.where(np.asarray(want_present), np.asarray(want_vals), 0)
    np.testing.assert_allclose(got[:m, :n], want, rtol=1e-5, atol=1e-5)
    print("row_parallel OK")

    # ---- ring-SUMMA masked matmul -----------------------------------------
    m2, k2, n2 = 32, 64, 40
    a = rng.standard_normal((m2, k2)).astype(np.float32)
    b = rng.standard_normal((k2, n2)).astype(np.float32)
    mask = (rng.random((m2, n2)) < 0.5).astype(np.float32)
    got = ring_masked_matmul(jnp.asarray(a), jnp.asarray(b),
                             jnp.asarray(mask), mesh, axis="data")
    want = np.where(mask != 0, a @ b, 0.0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    print("ring_summa OK")

    # ---- ring-SUMMA tile skipping: fully-masked column panels -------------
    # block=8 -> 5 column panels of the 40-wide output; panels 1 and 3 are
    # fully masked out and must be skipped (and still come out zero)
    mask2 = mask.copy()
    mask2[:, 8:16] = 0.0
    mask2[:, 24:32] = 0.0
    got = ring_masked_matmul(jnp.asarray(a), jnp.asarray(b),
                             jnp.asarray(mask2), mesh, axis="data", block=8)
    want = np.where(mask2 != 0, a @ b, 0.0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(got)[:, 8:16]).sum() == 0.0
    print("ring_summa_skip OK")

    # HLO must contain collective-permute (the overlap schedule exists)
    lowered = jax.jit(
        lambda a, b, mk: ring_masked_matmul(a, b, mk, mesh)).lower(
        jax.ShapeDtypeStruct((m2, k2), jnp.float32),
        jax.ShapeDtypeStruct((k2, n2), jnp.float32),
        jax.ShapeDtypeStruct((m2, n2), jnp.float32))
    txt = lowered.compile().as_text()
    assert "collective-permute" in txt, "ring rotation missing from HLO"
    print("hlo OK")


if __name__ == "__main__":
    main()
    print("DIST_ALL_OK")
