"""Distributed grid: sparse ring vs dense ring vs row-parallel over
B-density x mesh size (forced host devices).

On a host with enough devices (a four-chip TPU host) the grid runs in
this process.  On the CPU the parent must keep seeing one device (the
other benches depend on it), so the grid runs in a child interpreter with
``--xla_force_host_platform_device_count``, exactly like the distributed
tests.  Points are block-structured operands (the tile pipeline's regime:
whole ``bs x bs`` tiles on/off, dense within) at several B tile densities,
plus a uniform-ER control where the row route must keep winning.  The
child writes ``results/bench/dist_grid.json``:

* per point/mesh: wall time of the sparse BCSR ring
  (``ring_sparse_masked_spgemm``), the dense ring (``ring_masked_matmul``
  on pre-materialized dense operands — generous to it: its densify cost is
  not billed), and the row-parallel route, plus the distributed planner's
  election;
* ``_sparse_beats_dense_somewhere`` — the sparse ring beats the dense ring
  on at least one sparse-B point (B tile density <= ``SPARSE_B_TD``);
* ``_auto_ok`` — at every point the elected route is within
  ``PICK_TOLERANCE`` of the measured best route.

Re-tune ``planner.DIST_COST`` against this grid (see ROADMAP "Open
items").
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

#: a point fails if the elected route is slower than (1 + this) x best
PICK_TOLERANCE = 0.10
#: B tile densities at or below this count as "sparse-B" for the
#: sparse-vs-dense-ring acceptance flag
SPARSE_B_TD = 0.05


def _child(n: int, mesh_sizes, densities_b, iters: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.distributed import (distributed_masked_spgemm,
                                        ring_masked_matmul,
                                        ring_sparse_masked_spgemm)
    from repro.core.formats import block_sparse, csr_from_dense, erdos_renyi
    from repro.core.planner import collect_stats, decide_distributed
    from .common import save, timeit

    bs = 32

    points = [(f"block_tdb{td}", block_sparse(n, bs, 0.1, 0.9, seed=1),
               block_sparse(n, bs, td, 0.9, seed=2),
               block_sparse(n, bs, 0.2, 1.0, seed=3, mask=True),
               td) for td in densities_b]
    # uniform-ER control: no block structure, the row route must win and
    # the planner must keep the ring unelected
    points.append(("er_control", erdos_renyi(n, 8, seed=1).to_dense(),
                   erdos_renyi(n, 8, seed=2).to_dense(),
                   erdos_renyi(n, 8, seed=3).to_dense(), None))

    table = {}
    sparse_beats_dense = False
    auto_ok = True
    for pname, A, B, M, td in points:
        Ac, Bc, Mc = (csr_from_dense(np.asarray(A)),
                      csr_from_dense(np.asarray(B)),
                      csr_from_dense(np.asarray(M)))
        a_d, b_d, m_d = (jnp.asarray(A), jnp.asarray(B), jnp.asarray(M))
        stats = collect_stats(Ac, Bc, Mc)
        for p in mesh_sizes:
            mesh = Mesh(np.array(jax.devices()[:p]), ("data",))
            dplan = decide_distributed(stats, p)

            def go_sparse():
                out = ring_sparse_masked_spgemm(
                    Ac, Bc, Mc, mesh, block_size=dplan.tile_block or None)
                out.vals.block_until_ready()

            def go_dense():
                out = ring_masked_matmul(a_d, b_d, m_d, mesh, axis="data")
                out.block_until_ready()

            def go_row():
                out = distributed_masked_spgemm(
                    Ac, Bc, Mc, mesh, algorithm="row",
                    row_algorithm=dplan.row_algorithm)
                out.vals.block_until_ready()

            times = {"ring": timeit(go_sparse, iters=iters),
                     "ring_dense": timeit(go_dense, iters=iters),
                     # the row route loses by construction off the control
                     # point and can take tens of seconds there — one
                     # timed call is plenty to establish the ranking
                     "row": timeit(go_row,
                                   iters=1 if td is not None else iters)}
            best = min(("ring", "row"), key=times.get)
            point_ok = times[dplan.route] <= (1 + PICK_TOLERANCE) * times[best]
            auto_ok &= point_ok
            if td is not None and td <= SPARSE_B_TD \
                    and times["ring"] < times["ring_dense"]:
                sparse_beats_dense = True
            name = f"{pname}_p{p}"
            table[name] = {
                "n": n, "tile_density_b": td, "p": p, "times": times,
                "chosen": dplan.route, "tile_block": dplan.tile_block,
                "modeled": dict(dplan.costs), "best": best, "ok": point_ok,
            }
            print(f"[dist] {name:22s} ring={times['ring'] * 1e3:7.1f}ms "
                  f"dense={times['ring_dense'] * 1e3:7.1f}ms "
                  f"row={times['row'] * 1e3:7.1f}ms "
                  f"chosen={dplan.route:4s} "
                  f"{'OK' if point_ok else 'MISS'}", flush=True)
    table["_sparse_beats_dense_somewhere"] = sparse_beats_dense
    table["_auto_ok"] = auto_ok
    print(f"[dist] sparse_beats_dense_somewhere={sparse_beats_dense} "
          f"auto_ok={auto_ok}", flush=True)
    save("dist_grid", table)


def run(n: int = 2048, mesh_sizes=(2, 4, 8),
        densities_b=(0.02, 0.1, 0.3), iters: int = 3) -> dict:
    """Measure the grid (in-process, or in the forced-multi-device child
    on the CPU) and return the written grid."""
    from repro.tuning.probes import measure_in_process
    from .common import results_dir
    if measure_in_process(max(mesh_sizes)):
        _child(n, mesh_sizes, densities_b, iters)
    else:
        _spawn_child(n, mesh_sizes, densities_b, iters)
    with open(os.path.join(results_dir(), "dist_grid.json")) as f:
        return json.load(f)


def _spawn_child(n: int, mesh_sizes, densities_b, iters: int) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{max(mesh_sizes)} " + env.get("XLA_FLAGS", ""))
    spec = json.dumps({"n": n, "mesh_sizes": list(mesh_sizes),
                       "densities_b": list(densities_b), "iters": iters})
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_dist", "--child", spec],
        env=env, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(f"bench_dist child failed: {proc.returncode}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + 1 iteration (CI smoke job)")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child is not None:
        spec = json.loads(args.child)
        _child(spec["n"], spec["mesh_sizes"], spec["densities_b"],
               spec["iters"])
    elif args.smoke:
        run(n=256, mesh_sizes=(2, 4), densities_b=(0.02, 0.3), iters=1)
    else:
        run()


if __name__ == "__main__":
    main()
