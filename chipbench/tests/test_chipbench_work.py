"""The required-work count of a masked product, against brute force."""
import itertools
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import work  # noqa: E402


def _csr(dense):
    n = dense.shape[0]
    indptr = np.concatenate([[0], np.cumsum((dense != 0).sum(1))])
    return indptr, np.nonzero(dense)[1], (n, dense.shape[1])


def _brute(a, b, m):
    n, k = a.shape
    return sum(1 for i, kk, j in itertools.product(range(n), range(k),
                                                    range(b.shape[1]))
               if a[i, kk] and b[kk, j] and m[i, j])


@pytest.mark.parametrize("seed", range(4))
def test_products_in_mask_brute_force(seed):
    rng = np.random.default_rng(seed)
    a, b, m = (rng.random((9, 9)) < d for d in (0.3, 0.4, 0.5))
    assert work.products_in_mask(_csr(a), _csr(b), _csr(m)) == _brute(a, b, m)


def test_triangle_count_is_products_in_lower_mask():
    rng = np.random.default_rng(0)
    sym = np.triu(rng.random((12, 12)) < 0.4, 1)
    sym = sym | sym.T
    low = np.tril(sym, -1)
    tri = int(np.trace(sym.astype(int) @ sym @ sym)) // 6
    triple = _csr(low)
    assert work.products_in_mask(triple, triple, triple) == tri


def test_required_bytes_and_bound():
    need = work.required(nrows=10, nnz_a=20, nnz_b=30, nnz_m=40,
                         products=7)
    csr = lambda nnz: nnz * 8 + 11 * 4  # noqa: E731
    assert need == {"flops": 14,
                    "bytes": csr(20) + csr(30) + csr(40) + 40 * 5}
    peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    t, bound = work.roofline_seconds(need, peaks)
    assert bound == "memory" and t == need["bytes"] / 1e9
    t, bound = work.roofline_seconds({"flops": 10 ** 13, "bytes": 1}, peaks)
    assert bound == "compute" and t == 10.0
