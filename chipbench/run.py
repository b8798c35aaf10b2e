#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are those ``BENCHMARK.json`` names.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics with ``--trace 0``, the per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``, each number compared with the reference beside its
limit.  Without a TPU that ``peaks.json`` lists, or with fewer chips than
the cell needs, it prints no result and exits nonzero.

JAX's persistent compilation cache lives in the checkout, at the fixed
path ``<checkout>/.jax_cache``, handed to the program (and JAX) through
``JAX_COMPILATION_CACHE_DIR``: only a cell's first run in a checkout
compiles, and two checkouts never share a cache.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
