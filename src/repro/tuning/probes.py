"""Microbenchmark probe suite: the measurements the fit solves against.

Three probe families mirror the three constant tables:

* ``row``  — every vmapped row kernel on an ER input-degree x mask-degree
  grid (the same family ``benchmarks/bench_density.py`` sweeps), solving
  for ``accumulators.COST_CONSTANTS``;
* ``tile`` — the end-to-end BCSR tile route on block-structured operands
  plus uniform-ER controls (``benchmarks/bench_tile.py``'s families), with
  one reference row-kernel timing per point, solving for
  ``planner.TILE_COST`` and informing the ``TILE_MIN_*`` gates;
* ``dist`` — the row-parallel and sparse-ring distributed routes over a
  B-density x mesh-size grid (``benchmarks/bench_dist.py``'s family),
  solving for ``planner.DIST_COST``.  Runs in a forced-host-device child
  interpreter when the process does not already see enough devices.

Grids are sized for minutes, not hours: calibration needs the cost
*slopes*, not benchmark-grade precision — the planner's measured-trial
fallback already absorbs near-tie noise at plan time.  The generators
(``erdos_renyi``, ``er_mask``, ``block_sparse``) live in
``repro.core.formats`` and are the SAME functions the benchmarks sweep,
so profiles are fit against the distributions the acceptance grids
measure.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

#: probe families, in fit order (tile consumes row's fit, dist both)
FAMILIES = ("row", "tile", "dist")


@dataclasses.dataclass(frozen=True)
class Measurement:
    """One timed probe point.

    ``features`` carries the PlanStats fields (plus family extras such as
    ``bs``/``p``) the fit needs to rebuild the model's feature vector —
    the probe records *what was measured*, the fit decides *how to use
    it*.
    """

    family: str          # "row" | "tile" | "dist"
    target: str          # algorithm or route that was timed
    point: str           # grid-point label (diagnostics)
    seconds: float       # min-of-k wall seconds
    features: Dict[str, float]

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Measurement":
        return cls(family=d["family"], target=d["target"], point=d["point"],
                   seconds=float(d["seconds"]), features=dict(d["features"]))


def _min_time(fn, iters: int, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _stats_features(stats) -> Dict[str, float]:
    return {k: float(v) if not isinstance(v, (str, bool)) else v
            for k, v in dataclasses.asdict(stats).items()}


# ---------------------------------------------------------------------------
# Row-kernel probes
# ---------------------------------------------------------------------------


def probe_row(*, smoke: bool = False,
              log=print) -> List[Measurement]:
    """Time every row kernel on an ER degree grid; one Measurement per
    (point, algorithm)."""
    from repro.core.formats import er_mask, erdos_renyi
    from repro.core.masked_spgemm import ALGORITHMS, masked_spgemm
    from repro.core.planner import collect_stats

    if smoke:
        grid = [(256, (2, 8), (2, 8), 1)]
    else:
        grid = [(512, (2, 8, 32), (2, 8, 32), 2),
                (1024, (2, 8, 32), (2, 8, 32), 2)]
    out: List[Measurement] = []
    for n, degrees, mask_degrees, iters in grid:
        for d in degrees:
            A = erdos_renyi(n, d, seed=10 + d)
            B = erdos_renyi(n, d, seed=20 + d)
            for dm in mask_degrees:
                M = er_mask(n, dm, seed=30 + dm)
                stats = collect_stats(A, B, M)
                feats = _stats_features(stats)
                point = f"row_n{n}_d{d}_m{dm}"
                for algo in ALGORITHMS:
                    def go(algo=algo):
                        r = masked_spgemm(A, B, M, algorithm=algo)
                        r.vals.block_until_ready()
                    secs = _min_time(go, iters)
                    out.append(Measurement("row", algo, point, secs, feats))
                log(f"[tune/row] {point}: " + " ".join(
                    f"{m.target}={m.seconds * 1e3:.1f}ms"
                    for m in out[-len(ALGORITHMS):]))
    return out


# ---------------------------------------------------------------------------
# Tile-route probes
# ---------------------------------------------------------------------------


def probe_tile(*, smoke: bool = False,
               log=print) -> List[Measurement]:
    """Time the BCSR tile route (and, per point, the modeled-best row
    kernel as the win/loss reference the gate fit needs)."""
    from repro.core.formats import (block_sparse, csr_from_dense, er_mask,
                                    erdos_renyi)
    from repro.core.masked_spgemm import masked_spgemm
    from repro.core.planner import collect_stats, rank_algorithms

    if smoke:
        n, block_sizes, tds, mos, iters = 128, (8, 16), (0.3,), (0.5,), 1
    else:
        n, block_sizes, tds, mos, iters = 512, (8, 32), (0.1, 0.3), \
            (0.2, 0.6), 2
    out: List[Measurement] = []
    for bs in block_sizes:
        points = [
            (f"tile_bs{bs}_td{td}_mo{mo}",
             block_sparse(n, bs, td, 0.9, seed=100 + bs),
             block_sparse(n, bs, td, 0.9, seed=200 + bs),
             block_sparse(n, bs, mo, 1.0, seed=300 + int(mo * 10),
                          mask=True))
            for td in tds for mo in mos
        ]
        # uniform-ER control: the regime the gates must keep OUT of the
        # tile route — its loss margin anchors the density/occupancy fit
        points.append((f"tile_bs{bs}_er_control",
                       erdos_renyi(n, 4, seed=bs).to_dense(),
                       erdos_renyi(n, 4, seed=bs + 1).to_dense(),
                       er_mask(n, 8, seed=bs + 2).to_dense()))
        for point, A, B, M in points:
            Ac, Bc, Mc = (csr_from_dense(np.asarray(A)),
                          csr_from_dense(np.asarray(B)),
                          csr_from_dense(np.asarray(M)))
            stats = collect_stats(Ac, Bc, Mc)
            feats = dict(_stats_features(stats), bs=float(bs))

            def go_tile():
                r = masked_spgemm(Ac, Bc, Mc, algorithm="tile",
                                  tile_block=bs)
                r.vals.block_until_ready()

            t_tile = _min_time(go_tile, iters)
            out.append(Measurement("tile", "tile", point, t_tile, feats))
            row_alg = rank_algorithms(stats)[0][0]

            def go_row():
                r = masked_spgemm(Ac, Bc, Mc, algorithm=row_alg)
                r.vals.block_until_ready()

            t_row = _min_time(go_row, iters)
            out.append(Measurement("tile", f"row:{row_alg}", point, t_row,
                                   feats))
            log(f"[tune/tile] {point}: tile={t_tile * 1e3:.1f}ms "
                f"{row_alg}={t_row * 1e3:.1f}ms")
    return out


# ---------------------------------------------------------------------------
# Distributed probes (forced-host-device child when needed)
# ---------------------------------------------------------------------------


def _dist_spec(smoke: bool) -> dict:
    if smoke:
        return dict(n=256, mesh_sizes=(2, 4), densities_b=(0.02, 0.3),
                    iters=1)
    return dict(n=1024, mesh_sizes=(2, 4), densities_b=(0.02, 0.1, 0.3),
                iters=2)


def _measure_dist(n: int, mesh_sizes: Sequence[int],
                  densities_b: Sequence[float], iters: int,
                  log=print) -> List[Measurement]:
    """Measure ring + row routes; assumes enough jax devices exist."""
    import jax
    from jax.sharding import Mesh

    from repro.core.distributed import (distributed_masked_spgemm,
                                        ring_sparse_masked_spgemm)
    from repro.core.formats import block_sparse, csr_from_dense, erdos_renyi
    from repro.core.planner import collect_stats, decide_distributed

    bs = 32
    points = [(f"dist_tdb{td}",
               block_sparse(n, bs, 0.1, 0.9, seed=1),
               block_sparse(n, bs, td, 0.9, seed=2),
               block_sparse(n, bs, 0.2, 1.0, seed=3, mask=True))
              for td in densities_b]
    points.append(("dist_er_control",
                   erdos_renyi(n, 8, seed=1).to_dense(),
                   erdos_renyi(n, 8, seed=2).to_dense(),
                   erdos_renyi(n, 8, seed=3).to_dense()))
    out: List[Measurement] = []
    for point, A, B, M in points:
        Ac, Bc, Mc = (csr_from_dense(np.asarray(A)),
                      csr_from_dense(np.asarray(B)),
                      csr_from_dense(np.asarray(M)))
        stats = collect_stats(Ac, Bc, Mc)
        base_feats = _stats_features(stats)
        for p in mesh_sizes:
            mesh = Mesh(np.array(jax.devices()[:p]), ("data",))
            dplan = decide_distributed(stats, p)
            ring_bs = dplan.tile_block or bs
            feats = dict(base_feats, p=float(p), bs=float(ring_bs),
                         row_algorithm=dplan.row_algorithm)

            def go_ring():
                r = ring_sparse_masked_spgemm(Ac, Bc, Mc, mesh,
                                              block_size=ring_bs)
                r.vals.block_until_ready()

            def go_row():
                r = distributed_masked_spgemm(
                    Ac, Bc, Mc, mesh, algorithm="row",
                    row_algorithm=dplan.row_algorithm)
                r.vals.block_until_ready()

            pt = f"{point}_p{p}"
            t_ring = _min_time(go_ring, iters)
            out.append(Measurement("dist", "ring", pt, t_ring, feats))
            t_row = _min_time(go_row, iters)
            out.append(Measurement("dist", "row", pt, t_row, feats))
            log(f"[tune/dist] {pt}: ring={t_ring * 1e3:.1f}ms "
                f"row={t_row * 1e3:.1f}ms ({dplan.row_algorithm})")
    return out


def measure_in_process(need: int) -> bool:
    """Whether a ``need``-device measurement runs in this process.

    True when the process already sees that many devices.  False on the
    CPU, where a child interpreter with
    ``--xla_force_host_platform_device_count`` stands in (the device count
    is frozen at first use and cannot be raised in-process).  On an
    accelerator with fewer devices it raises: a child would need the chip
    this process already holds, and would fail or hang.
    """
    import jax

    have = len(jax.devices())
    if have >= need:
        return True
    if jax.default_backend() == "cpu":
        return False
    raise RuntimeError(f"{need} devices needed; this host has {have} "
                       f"{jax.default_backend()} device(s)")


def probe_dist(*, smoke: bool = False, log=print) -> List[Measurement]:
    """Distributed probes: in-process when this process sees the largest
    probed mesh, otherwise (CPU only) in a forced-host-device child."""
    spec = _dist_spec(smoke)
    if measure_in_process(max(spec["mesh_sizes"])):
        return _measure_dist(log=log, **spec)

    out_path = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                            f"repro_tune_dist_{os.getpid()}.json")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{max(spec['mesh_sizes'])} "
                        + env.get("XLA_FLAGS", ""))
    child_spec = json.dumps(dict(spec, out=out_path))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.tuning.probes", "--dist-child",
         child_spec], env=env, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(f"dist probe child failed: {proc.returncode}")
    try:
        with open(out_path) as f:
            records = json.load(f)
    finally:
        try:
            os.remove(out_path)
        except OSError:
            pass
    return [Measurement.from_dict(r) for r in records]


def run_probes(families: Sequence[str], *, smoke: bool = False,
               log=print) -> List[Measurement]:
    """Run the selected probe families in canonical order."""
    unknown = sorted(set(families) - set(FAMILIES))
    if unknown:
        raise ValueError(f"unknown probe families {unknown}; "
                         f"valid: {list(FAMILIES)}")
    runners = {"row": probe_row, "tile": probe_tile, "dist": probe_dist}
    out: List[Measurement] = []
    for fam in FAMILIES:
        if fam in families:
            out.extend(runners[fam](smoke=smoke, log=log))
    return out


def _main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="probe child entry (used by repro.tune; not a CLI)")
    ap.add_argument("--dist-child", required=True)
    args = ap.parse_args(argv)
    spec = json.loads(args.dist_child)
    ms = _measure_dist(spec["n"], spec["mesh_sizes"], spec["densities_b"],
                       spec["iters"])
    with open(spec["out"], "w") as f:
        json.dump([m.to_dict() for m in ms], f)
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
