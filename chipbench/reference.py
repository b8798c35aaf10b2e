"""Plain reference for the triangle count, and its lower-precision control.

Triangle counting as GAP and the paper state it: vertices in
non-increasing degree order (ties by vertex id), ``L`` the strictly lower
triangle of the relabelled adjacency, and ``C = L .* (L @ L)``.  Entry
``C[i, j]`` of edge ``(i, j)`` is its support, the number of ``k`` with
``j < k < i`` adjacent to both; the triangle count is the sum of ``C``.

Everything here is int64 scipy and numpy arithmetic on the input arrays.
It imports nothing of the program and takes nothing the program made.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp


@dataclasses.dataclass(frozen=True)
class Answer:
    """Per-edge result over ``L``'s entries in row-major order."""

    indptr: np.ndarray   # L's row pointers (relabelled vertex ids)
    indices: np.ndarray  # L's column ids, sorted within each row
    support: np.ndarray  # int64 support of each edge of L
    count: int           # triangles: the sum of ``support``


def lower_triangle(indptr: np.ndarray, indices: np.ndarray):
    """``L`` of the degree-ordered graph as sorted CSR arrays."""
    n = len(indptr) - 1
    deg = np.diff(indptr)
    order = np.argsort(-deg, kind="stable")
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    rows = rank[np.repeat(np.arange(n, dtype=np.int64), deg)]
    cols = rank[indices]
    keep = cols < rows
    key = np.sort(rows[keep] * n + cols[keep])
    lo_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=lo_ptr[1:])
    return lo_ptr, key % n


def triangles(indptr: np.ndarray, indices: np.ndarray) -> Answer:
    """Exact per-edge supports and triangle count of an adjacency."""
    lo_ptr, lo_idx = lower_triangle(indptr, indices)
    n = len(lo_ptr) - 1
    low = sp.csr_matrix((np.ones(len(lo_idx), np.int64), lo_idx, lo_ptr),
                        shape=(n, n))
    paths = (low @ low).multiply(low).tocsr()
    paths.sort_indices()
    paths.eliminate_zeros()
    # place each nonzero support on its edge of L: both are row-major
    # sorted, and the supports' pattern is a subset of L's
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(lo_ptr))
    keys = rows * n + lo_idx
    p_rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(paths.indptr))
    at = np.searchsorted(keys, p_rows * n + paths.indices)
    support = np.zeros(len(lo_idx), np.int64)
    support[at] = paths.data
    return Answer(lo_ptr, lo_idx, support, int(support.sum()))


def bf16_control(ref: Answer):
    """The reference computed in bfloat16, the nearest precision below the
    configurations' float32: each edge's support and the count are summed
    in bfloat16, one addition after another.

    Products of 0/1 operands are exact in any precision, and a support of
    at most 256 is exact in bfloat16, so per-edge values change only above
    256.  The count does not survive: a running bfloat16 sum stops growing
    once the next support is under half a unit in its last place.  Returns
    ``(values, present, count)`` in the form a solve returns them.
    """
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    # a support s is s additions of 1.0 in bfloat16: exact to 256, where
    # 256 + 1 rounds back to 256 (ties to even)
    vals = np.minimum(ref.support, 256).astype(bf16)
    count = np.cumsum(vals, dtype=bf16)[-1] if len(vals) else bf16(0)
    return (vals.astype(np.float32), ref.support > 0,
            float(np.float32(count)))
