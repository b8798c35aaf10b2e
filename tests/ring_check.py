"""Subprocess body for the sparse ring's entry-point tests: 4 host devices.

Run as:  python tests/ring_check.py <check>
(invoked by tests/test_ring.py).  Triangle counting on degree-ordered
Kronecker graphs through ``masked_spgemm(..., devices=4)``, checked edge
by edge against the benchmark's int64 scipy reference.
"""
import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", ""))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import graphs, reference  # noqa: E402
from repro import obs  # noqa: E402
from repro.core import masked_spgemm  # noqa: E402
from repro.core.distributed import (device_mesh,  # noqa: E402
                                    ring_sparse_masked_spgemm)
from repro.core.formats import CSR, tril  # noqa: E402
from repro.graphs.triangle_counting import degree_relabel  # noqa: E402


def kronecker(scale, isolated=0):
    """GAP kron at ``scale`` with ``isolated`` vertices added, which the
    degree order puts last, as Kronecker's own isolated vertices are at
    scale 17."""
    ip, ix = graphs.kronecker(scale, 16, 1, a=0.57, b=0.19, c=0.19)
    return np.concatenate([ip, np.full(isolated, ip[-1])]), ix


def solve(ip, ix, bs, devices=4, **kw):
    """The ring's triangle count, its ``spgemm.ring_prep`` attributes,
    and a check of every edge against the reference."""
    n = len(ip) - 1
    adj = CSR(ip, ix, np.ones(len(ix), np.float32), (n, n))
    L = tril(degree_relabel(adj), strict=True)
    with obs.tracing() as tr:
        if kw:
            res = ring_sparse_masked_spgemm(L, L, L, device_mesh(devices),
                                            block_size=bs, **kw)
        else:
            res = masked_spgemm(L, L, L, algorithm="ring", tile_block=bs,
                                devices=devices)
    ref = reference.triangles(ip, ix)
    assert np.array_equal(L.indptr, ref.indptr)
    assert np.array_equal(L.indices, ref.indices)
    rows = np.repeat(np.arange(n), np.diff(L.indptr))
    slots = np.arange(L.nnz) - L.indptr[rows]
    vals = np.asarray(res.vals)[rows, slots]
    present = np.asarray(res.present)[rows, slots]
    cols = np.asarray(res.mask_cols)[rows, slots]
    assert np.array_equal(vals, ref.support), "values differ"
    assert np.array_equal(present, ref.support > 0), "presence differs"
    assert np.array_equal(cols, ref.indices), "columns differ"
    assert float(vals[present].sum(dtype=np.float64)) == ref.count
    preps = [s["attrs"] for s in tr.sink.spans()
             if s["name"] == "spgemm.ring_prep"]
    return ref.count, preps


def exact_counts():
    """Scales 8-10 at the cell's block size (lane-dense blocks) and at
    sizes stored as (bs, bs); one Pallas run in interpret mode."""
    for scale in (8, 9, 10):
        for bs in (32, 16):
            count, _ = solve(*kronecker(scale), bs)
            print(f"scale {scale} bs {bs}: {count} triangles")
    solve(*kronecker(8), 32, backend="pallas", interpret=True)
    print("exact_counts OK")


def every_device_holds_blocks():
    """With a quarter of the vertices isolated the equal-row split leaves
    the last device without blocks; the round-robin partition does not."""
    ip, ix = kronecker(9, isolated=512)
    bs, p = 32, 4
    lo_ptr, lo_idx = reference.lower_triangle(ip, ix)
    n = len(lo_ptr) - 1
    rows = np.repeat(np.arange(n), np.diff(lo_ptr))
    block_rows = np.unique((rows // bs) * n + lo_idx // bs) // n
    per_row = np.bincount(block_rows, minlength=-(-n // bs))
    equal = [int(x.sum()) for x in np.array_split(per_row, p)]
    assert equal[-1] == 0, equal
    _, (prep,) = solve(ip, ix, bs)
    assert prep["p"] == p and prep["bs"] == bs
    assert min(prep["blocks"]) > 0 and min(prep["entries"]) > 0, prep
    assert sum(prep["blocks"]) == sum(equal)
    print("every_device_holds_blocks OK", equal, prep["blocks"])


def indivisible_block_rows():
    """Block-row counts that the ring size does not divide: 16 block rows
    over 3 devices, and 293 rows (10 block rows, the last partial) over 4."""
    solve(*kronecker(9), 32, devices=3)
    solve(*kronecker(8, isolated=37), 32)
    print("indivisible_block_rows OK")


def signed_with_stored_zeros(rng, n, density):
    """CSR with values in {-1, 0, 1, 2}, every 0 explicitly stored."""
    from repro.core.formats import csr_from_coo
    rows, cols = np.nonzero(rng.random((n, n)) < density)
    vals = rng.integers(-1, 3, len(rows)).astype(np.float32)
    return csr_from_coo(rows, cols, vals, (n, n), sum_dups=False)


def stored_zeros_and_cancellation():
    """Stored zeros and cancelling pairs: the ring's values and presence
    equal the row kernels', on the XLA executor and on the Pallas one
    (interpret mode), whose prep span reports one walk over both planes
    and the busiest device's grid steps in whole chunks."""
    from repro.core.distributed import clear_ring_prep_cache
    rng = np.random.default_rng(31)
    A, B, M = (signed_with_stored_zeros(rng, 96, d) for d in (0.1, 0.1, 0.3))
    want = masked_spgemm(A, B, M, algorithm="msa")
    present = np.asarray(want.present)
    assert (present & (np.asarray(want.vals) == 0)).any(), "no cancellation"
    clear_ring_prep_cache()
    with obs.tracing() as tr:
        pallas = ring_sparse_masked_spgemm(A, B, M, device_mesh(4),
                                           block_size=8, backend="pallas",
                                           interpret=True)
    xla = masked_spgemm(A, B, M, algorithm="ring", tile_block=8, devices=4)
    for got in (pallas, xla):
        assert np.array_equal(np.asarray(got.present), present)
        assert np.array_equal(np.asarray(got.vals), np.asarray(want.vals))
    (prep,) = [s["attrs"] for s in tr.sink.spans()
               if s["name"] == "spgemm.ring_prep"]
    length = max(prep["stage_entries"])     # under one chunk a stage
    assert prep["planes"] == 2 and prep["steps"] % length == 0, prep
    assert max(prep["entries"]) <= prep["steps"] <= 4 * length, prep
    print("stored_zeros_and_cancellation OK", prep["steps"])


CHECKS = {f.__name__: f for f in (exact_counts, every_device_holds_blocks,
                                  indivisible_block_rows,
                                  stored_zeros_and_cancellation)}

if __name__ == "__main__":
    assert jax.device_count() == 4, jax.devices()
    CHECKS[sys.argv[1]]()
    print("RING_CHECK_OK")
