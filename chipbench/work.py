"""What a masked product ``C = M .* (A @ B)`` requires, whatever computes it.

The roofline of a kernel that computes it is the least time the chip could
take for this work.  The count depends only on the operands, never on the
block size, the number of replays or the kernel that runs, so it does not
go stale when a later change alters those.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

#: bytes of one stored value (float32), one column id or row pointer
#: (int32, as the device holds them), one presence flag (bool)
VALUE_BYTES, INDEX_BYTES, FLAG_BYTES = 4, 4, 1


def _pattern(indptr, indices, shape):
    return sp.csr_matrix((np.ones(len(indices), np.int64), indices, indptr),
                         shape=shape)


def products_in_mask(a, b, m) -> int:
    """Number of products ``a_ik * b_kj`` whose ``(i, j)`` is in the mask.

    ``a``, ``b`` and ``m`` are ``(indptr, indices, shape)`` triples.  For
    triangle counting (``A = B = M = L``) this is the triangle count.
    """
    pa, pb, pm = (_pattern(*x) for x in (a, b, m))
    return int((pa @ pb).multiply(pm).sum())


def csr_bytes(nrows: int, nnz: int) -> int:
    """Bytes of one CSR operand: values, column ids and row pointers."""
    return nnz * (VALUE_BYTES + INDEX_BYTES) + (nrows + 1) * INDEX_BYTES


def required(nrows: int, nnz_a: int, nnz_b: int, nnz_m: int,
             products: int) -> dict:
    """Flops and bytes the masked product requires.

    Flops: a multiply and an add per product in the mask.  Bytes: A, B
    and M read once as CSR, and the mask-aligned output (a value and a
    presence flag per mask entry) written once.  The operands are square
    here (``nrows`` rows each), as in triangle counting.
    """
    return {
        "flops": 2 * products,
        "bytes": (csr_bytes(nrows, nnz_a) + csr_bytes(nrows, nnz_b)
                  + csr_bytes(nrows, nnz_m)
                  + nnz_m * (VALUE_BYTES + FLAG_BYTES)),
    }


def roofline_seconds(work: dict, peaks: dict) -> tuple:
    """``(seconds, bound)``: the least time the chip could take for
    ``work`` at its peaks, and which peak bounds it (``compute`` or
    ``memory``)."""
    t_flops = work["flops"] / peaks["flops_per_s"]
    t_bytes = work["bytes"] / peaks["bytes_per_s"]
    return ((t_flops, "compute") if t_flops >= t_bytes
            else (t_bytes, "memory"))
