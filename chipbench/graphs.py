"""The benchmark's own graph generators: GAP's ``kron`` and ``urand``.

Each returns a symmetric 0/1 adjacency without self-loops or duplicate
edges as CSR arrays ``(indptr, indices)``, rows sorted, int64.  They are
copies kept with the benchmark, so that a change to the program cannot
move the yardstick: ``kronecker`` draws exactly what the program's
Graph500 R-MAT generator drew when the configurations' statistics were
recorded.

A run's input is the configuration's graph, drawn from the configuration's
fixed ``graph_seed``, with its vertices renamed at random from the run's
``--seed`` (``permute``).  Every seed thus sends the same graph, and the
same work, under other vertex names.
"""
from __future__ import annotations

import numpy as np


def _csr_from_pairs(rows: np.ndarray, cols: np.ndarray, n: int):
    """Sorted, deduplicated CSR arrays of the directed pairs given."""
    key = np.unique(rows * n + cols)
    counts = np.bincount(key // n, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr, (key % n).astype(np.int64)


def _undirected(rows: np.ndarray, cols: np.ndarray, n: int):
    keep = rows != cols
    rows, cols = rows[keep], cols[keep]
    return _csr_from_pairs(np.concatenate([rows, cols]),
                           np.concatenate([cols, rows]), n)


def kronecker(scale: int, edge_factor: int, seed: int, *, a: float,
              b: float, c: float):
    """Graph500 Kronecker (R-MAT) graph: ``edge_factor * 2**scale`` edges,
    each placed by ``scale`` quadrant choices with probabilities a, b, c
    and 1 - a - b - c; self-loops dropped, symmetrised, deduplicated."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    rows = np.zeros(m, np.int64)
    cols = np.zeros(m, np.int64)
    for lvl in range(scale):
        r = rng.random(m)
        go_right = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        go_down = r >= a + b
        rows |= go_down.astype(np.int64) << lvl
        cols |= go_right.astype(np.int64) << lvl
    return _undirected(rows, cols, n)


def uniform(scale: int, edge_factor: int, seed: int):
    """GAP's ``urand``: ``edge_factor * 2**scale`` endpoint pairs drawn
    uniformly; self-loops dropped, symmetrised, deduplicated."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    pairs = rng.integers(0, n, size=(2, n * edge_factor), dtype=np.int64)
    return _undirected(pairs[0], pairs[1], n)


GENERATORS = {"kronecker": kronecker, "uniform": uniform}


def generate(graph: dict):
    """The configuration's graph: ``graph`` is the ``graph`` object of a
    configuration file (``generator``, ``scale``, ``edge_factor``,
    ``graph_seed`` and the generator's own parameters)."""
    params = {k: v for k, v in graph.items()
              if k not in ("generator", "graph_seed")}
    return GENERATORS[graph["generator"]](seed=graph["graph_seed"], **params)


def permute(indptr: np.ndarray, indices: np.ndarray, seed: int):
    """The same graph under a random renaming of its vertices drawn from
    ``seed`` (any whole number).

    The renaming keeps the order of vertices of equal degree, so ordering
    by degree, ties by name, gives every seed the same degree-ordered
    graph: the same work, the same program shapes (so the compile cache
    serves every seed), from a different input.
    """
    n = len(indptr) - 1
    deg = np.diff(indptr)
    new = np.random.default_rng(seed % 2 ** 64).permutation(n)
    # each degree class takes the names drawn for it, in its old order
    perm = np.empty(n, np.int64)
    perm[np.lexsort((np.arange(n), deg))] = new[np.lexsort((new, deg))]
    rows = np.repeat(np.arange(n, dtype=np.int64), deg)
    return _csr_from_pairs(perm[rows], perm[indices], n)
