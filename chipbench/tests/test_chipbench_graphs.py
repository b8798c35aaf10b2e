"""The benchmark's generators and reference reproduce the recorded graphs."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import graphs, reference  # noqa: E402

CONFIGS = sorted((ROOT / "chipbench" / "configs").glob("*.json"))


def _check_simple(indptr, indices):
    """Symmetric, no self-loops, no duplicates, rows sorted."""
    n = len(indptr) - 1
    rows = np.repeat(np.arange(n), np.diff(indptr))
    key = rows * n + indices
    assert np.all(np.diff(key) > 0)
    assert not np.any(rows == indices)
    assert np.array_equal(np.sort(indices * n + rows), key)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_configuration_stats_at_full_scale(path):
    """Each configuration's graph has the statistics its file records
    (for gap-kron-s16: the scale-16 Graph500 graph at seed 1)."""
    config = json.loads(path.read_text())
    indptr, indices = graphs.generate(config["graph"])
    _check_simple(indptr, indices)
    ref = reference.triangles(indptr, indices)
    widths = np.diff(ref.indptr)
    assert {"vertices": len(indptr) - 1, "nnz_L": len(ref.indices),
            "widest_L_row": int(widths.max()),
            "triangles": ref.count} == config["stats"]


def test_uniform_matches_gap_urand_at_scale_14():
    """Widest L row 28, mean L row 16, about 5.4k triangles."""
    indptr, indices = graphs.uniform(14, 16, seed=1)
    _check_simple(indptr, indices)
    ref = reference.triangles(indptr, indices)
    widths = np.diff(ref.indptr)
    assert int(widths.max()) == 28
    assert abs(widths.mean() - 16) < 0.05
    assert 5300 <= ref.count <= 5500


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11, 2 ** 40 + 3])
def test_permute_renames_but_keeps_the_work(seed):
    """Every seed gives another input with the same degree-ordered L."""
    base = graphs.kronecker(9, 16, seed=5, a=0.57, b=0.19, c=0.19)
    moved = graphs.permute(*base, seed)
    _check_simple(*moved)
    assert not np.array_equal(moved[1], base[1])
    a, b = reference.triangles(*base), reference.triangles(*moved)
    assert np.array_equal(a.indptr, b.indptr)
    assert np.array_equal(a.indices, b.indices)
    assert np.array_equal(a.support, b.support)
    again = graphs.permute(*base, seed)
    assert np.array_equal(again[1], moved[1])


def test_reference_supports_against_brute_force():
    indptr, indices = graphs.kronecker(6, 8, seed=3, a=0.57, b=0.19,
                                       c=0.19)
    ref = reference.triangles(indptr, indices)
    n = len(ref.indptr) - 1
    low = np.zeros((n, n), np.int64)
    rows = np.repeat(np.arange(n), np.diff(ref.indptr))
    low[rows, ref.indices] = 1
    want = (low @ low)[rows, ref.indices]
    assert np.array_equal(ref.support, want)
    dense = np.zeros((n, n), np.int64)
    dense[np.repeat(np.arange(n), np.diff(indptr)), indices] = 1
    assert ref.count == int(np.trace(dense @ dense @ dense)) // 6


def test_bf16_control_count_is_wrong():
    indptr, indices = graphs.uniform(10, 16, seed=2)
    ref = reference.triangles(indptr, indices)
    values, present, count = reference.bf16_control(ref)
    assert np.array_equal(values, ref.support)   # supports <= 256: exact
    assert np.array_equal(present, ref.support > 0)
    assert count != ref.count
