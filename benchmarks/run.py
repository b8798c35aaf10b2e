"""Benchmark aggregator: one module per paper figure + the tile-path bench.

  PYTHONPATH=src python -m benchmarks.run            # small CPU sizes
  PYTHONPATH=src python -m benchmarks.run --full     # larger suite
  PYTHONPATH=src python -m benchmarks.run --only density,triangle

Roofline (needs results/dryrun from repro.launch.dryrun):
  PYTHONPATH=src python -m benchmarks.roofline
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time
import traceback

ORDER = ("density", "planner", "tile", "dist", "serve", "incremental",
         "replay", "obs", "health", "triangle", "rmat", "scaling",
         "ktruss", "bc", "block")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + 1 iteration (CI smoke job); writes "
                         "to a scratch dir so the committed full-tier "
                         "grids under results/bench/ survive")
    ap.add_argument("--only", default="",
                    help=f"comma-separated subset of: {', '.join(ORDER)}")
    ap.add_argument("--strict", action="store_true",
                    help="fail when a bench reports a False acceptance "
                         "flag (its _-prefixed booleans, e.g. _all_ok)")
    args = ap.parse_args()
    if args.smoke and not os.environ.get("REPRO_BENCH_OUT"):
        # smoke tiers must never clobber the committed full-tier grids
        # (dist_grid.json/tile_grid.json are calibration artifacts); the
        # env var also reaches bench_dist's forced-device child process
        os.environ["REPRO_BENCH_OUT"] = tempfile.mkdtemp(
            prefix="repro-bench-smoke-")
        print(f"[smoke] writing results to "
              f"{os.environ['REPRO_BENCH_OUT']} (committed results/bench/ "
              f"untouched)", flush=True)
    if args.only:
        only = {name.strip() for name in args.only.split(",")
                if name.strip()}
        unknown = sorted(only - set(ORDER))
        if unknown or not only:
            raise SystemExit(
                f"benchmarks.run: unknown --only job names {unknown}; "
                f"valid names: {', '.join(ORDER)}")
    else:
        only = set(ORDER)

    from repro import compile_cache
    print(f"[cache] compile cache at {compile_cache.enable()}", flush=True)
    from . import (bench_bc, bench_block_kernel, bench_density, bench_dist,
                   bench_health, bench_incremental, bench_ktruss,
                   bench_obs, bench_planner, bench_replay,
                   bench_rmat_scale, bench_scaling, bench_serve,
                   bench_tile, bench_triangle)
    if args.smoke:
        density_kw = dict(n=256, degrees=(2, 8), mask_degrees=(2, 8),
                          iters=3)
        tile_kw = dict(n=128, block_sizes=(8, 16), tile_densities=(0.3,),
                       mask_occupancies=(0.5,), iters=1)
        dist_kw = dict(n=256, mesh_sizes=(2, 4), densities_b=(0.02, 0.3),
                       iters=1)
        serve_kw = dict(n=128, queries=16, n_structs=2, iters=2)
        # trims rounds/queries but NOT n: the >=5x readiness win is
        # scale-dependent (the cold rebuild it beats is O(mask nnz)), so
        # shrinking the structure would fail --strict for the wrong reason
        incremental_kw = dict(rounds=3, queries_per_round=2)
        # the golden trace is tiny; smoke trims timing iters + the knob grid
        replay_kw = dict(iters=1, smoke=True)
        # iters stays high even in smoke: the gate is a ratio of two
        # noisy ~ms passes; the median needs samples to converge
        obs_kw = dict(n=128, queries=16, iters=21, smoke=True)
        # n stays at 256 in smoke: the monitor's per-record aggregation
        # cost is fixed (~2us), so the pass must be long enough that 5%
        # of it clears the measurement noise floor (at n=128 the bar
        # equals the jitter and the gate coin-flips)
        health_kw = dict(n=256, queries=24, iters=21, smoke=True)
    else:
        density_kw = dict(n=2048 if args.full else 1024)
        tile_kw = dict(n=512)
        # full tier matches the committed dist_grid.json calibration run;
        # the default tier trims the grid like its neighbors do
        dist_kw = dict() if args.full else dict(n=1024, mesh_sizes=(2, 4),
                                                densities_b=(0.02, 0.3))
        serve_kw = dict(n=1024 if args.full else 512,
                        queries=96 if args.full else 48)
        incremental_kw = dict(n=2048 if args.full else 1024,
                              rounds=12 if args.full else 8)
        replay_kw = dict(iters=3, autotune_rounds=2 if args.full else 1)
        # heavier per-query work than serve_kw (the ~µs-per-span budget
        # amortizes to ~1% of an n=1024 pass) and many paired iterations:
        # the gate is a ratio of two noisy ~40ms passes, so the median
        # needs samples to converge under scheduler jitter (~5s total)
        obs_kw = dict(n=1024, queries=128 if args.full else 96, iters=61)
        # same scale story as obs_kw: the monitored-vs-plain ratio needs
        # ~60ms passes and many pairs to resolve a ~1% true cost
        health_kw = dict(n=1024, queries=96 if args.full else 48,
                         iters=61 if args.full else 41)
    jobs = {
        "density": lambda: bench_density.run(**density_kw),
        "planner": lambda: bench_planner.run(**density_kw),
        "tile": lambda: bench_tile.run(**tile_kw),
        "dist": lambda: bench_dist.run(**dist_kw),
        "serve": lambda: bench_serve.run(**serve_kw),
        "incremental": lambda: bench_incremental.run(**incremental_kw),
        "replay": lambda: bench_replay.run(**replay_kw),
        "obs": lambda: bench_obs.run(**obs_kw),
        "health": lambda: bench_health.run(**health_kw),
        "triangle": lambda: bench_triangle.run(small=not args.full),
        "rmat": lambda: bench_rmat_scale.run(
            scales=(8, 9, 10, 11, 12) if args.full else (8, 9, 10)),
        "scaling": lambda: bench_scaling.run(),
        "ktruss": lambda: bench_ktruss.run(small=not args.full),
        "bc": lambda: bench_bc.run(batch=64 if args.full else 16),
        "block": lambda: bench_block_kernel.run(),
    }
    failures = []
    for name in ORDER:
        if name not in only:
            continue
        print(f"\n===== bench: {name} =====", flush=True)
        t0 = time.time()
        try:
            table = jobs[name]()
            bad_flags = [k for k, v in table.items()
                         if k.startswith("_") and v is False] \
                if isinstance(table, dict) else []
            if args.strict and bad_flags:
                failures.append(f"{name}:{','.join(bad_flags)}")
                print(f"===== {name} FAILED acceptance flags "
                      f"{bad_flags} =====", flush=True)
            else:
                print(f"===== {name} done in {time.time() - t0:.1f}s =====",
                      flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")
    print("\nall benchmarks completed; results in results/bench/")


if __name__ == "__main__":
    main()
