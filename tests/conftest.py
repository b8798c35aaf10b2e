"""Shared test fixtures/utilities.

NOTE: XLA_FLAGS device-count forcing is deliberately NOT set here — smoke
tests and benches must see the 1 real CPU device. Distributed tests spawn
subprocesses with their own XLA_FLAGS.
"""
import numpy as np
import pytest

from repro.core.formats import CSR, csr_from_coo, csr_from_dense


#: planner statistics of the Graph500 triangle count at scale 16:
#: ``L .* (L @ L)`` for the degree-relabelled strict lower triangle of
#: ``formats.rmat(16, 16, seed=1)`` (65,536 vertices, 15,690,247 triangles)
SCALE16_TC = dict(m=65536, k=65536, n=65536, nnz_a=909403, nnz_b=909403,
                  nnz_m=909403, wa=247, wb=247, wbt=9866, pm=247,
                  flops=87537664.0, out_nnz=522240.0)


def random_csr(rng, m, n, density, dtype=np.float32, sorted_rows=True) -> CSR:
    a = (rng.random((m, n)) < density).astype(dtype)
    a *= rng.uniform(0.5, 1.5, size=(m, n)).astype(dtype)
    return csr_from_dense(a)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
