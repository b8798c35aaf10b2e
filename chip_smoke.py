#!/usr/bin/env python3
"""Run the masked-product stack end to end on the TPU through its entry points.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the mesh path only, on four chips

The deployment is Graph500 Kronecker triangle counting (Graph500
specification; GAP Benchmark Suite, arXiv:1508.03619): an R-MAT graph with
A, B, C = 0.57, 0.19, 0.19 and edge factor 16 at scale 16 (65,536
vertices, about 1M undirected edges), degree-relabelled, counted as
``sum(L .* (L @ L))`` over its strict lower triangle ``L``.  The routes
that run the vmapped row kernels take the same graph at ``ROW_SCALE``.

One chip runs four phases in this one process:

1. device check: a TPU, or a nonzero exit before anything else runs;
2. ``triangle_count(adj)`` on the planner's route (``algorithm="auto"``);
3. ``masked_spgemm(L, L, L, algorithm="tile")`` on the compiled Pallas
   kernel (never the XLA executor, never interpret mode);
4. ``QueryEngine`` serving two structures (one repeated, so the burst path
   runs) and one ``submit_triangle``.

``--four-chips`` runs only ``distributed_masked_spgemm`` on a 1-D mesh over
four devices: the ring route against the single-chip tile route, and the
row route against the single-chip planner route, each on device 0.  Every
count must equal an exact integer reference computed on the host with
scipy, independent of the code under test.  Any failure
exits nonzero.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

#: Graph500 Kronecker graph: scale (log2 vertices), edge factor, seed
SCALE, EDGE_FACTOR, SEED = 16, 16, 1
#: scale of the routes on the vmapped row kernels (planner, served triangle
#: count, four-chip row route).  On a v5e their scatters make one scale-16
#: call take minutes (mca: over 178 s), and the planner's measured trial
#: there makes eight such calls; at scale 12 the planner elects inner,
#: whose program the chip's compiler sizes at 4.0 GB
ROW_SCALE = 12
#: BCSR block size of the tile and ring routes: at 128 the scale-16
#: operands, patterns and outputs take ~13 GB of the 16 GB chip; at 32
#: they take ~4 GB
TILE_BLOCK = 32
#: serving values are positive and each output sums at most 17 products
#: (the widest A row) in f32, so rounding stays near 1e-6 relative; 1e-5
#: leaves a margin, also across routes whose summation orders differ
RTOL, ATOL = 1e-5, 1e-6


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def device_info(need: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu", f"no TPU: JAX found platform {d0.platform!r}")
    check(len(devs) >= need, f"{need} TPU devices needed, found {len(devs)}")
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def reference_triangles(adj) -> int:
    """Exact triangle count in int64 scipy arithmetic.  Any vertex order
    counts each triangle once in ``sum(L .* (L @ L))``; ordering by degree
    only keeps ``L @ L`` small."""
    import numpy as np
    import scipy.sparse as sp
    n = adj.shape[0]
    a = sp.csr_matrix((np.ones(adj.nnz, np.int64), adj.indices, adj.indptr),
                      shape=adj.shape)
    order = np.argsort(-np.diff(adj.indptr), kind="stable")
    a = a[order][:, order]
    low = sp.tril(a, k=-1).tocsr()
    assert low.shape == (n, n)
    return int(low.multiply(low @ low).sum())


def exact_count(res) -> int:
    """Sum of a 0/1 masked product: integer counts, exact in f32."""
    import numpy as np
    vals = np.asarray(res.vals, np.float64)
    return int(vals[np.asarray(res.present)].sum())


def build_graph(scale: int):
    from repro.core.formats import rmat, tril
    from repro.graphs.triangle_counting import degree_relabel
    t0 = time.perf_counter()
    adj = rmat(scale, EDGE_FACTOR, seed=SEED)
    L = tril(degree_relabel(adj), strict=True)
    log(f"[graph] rmat scale={scale} edge_factor={EDGE_FACTOR} seed={SEED}: "
        f"{adj.shape[0]} vertices, {adj.nnz // 2} edges, nnz(L)={L.nnz}, "
        f"widest row {int(L.row_nnz().max())} "
        f"({time.perf_counter() - t0:.1f}s)")
    t0 = time.perf_counter()
    ref = reference_triangles(adj)
    log(f"[reference] scipy int64 triangles={ref} "
        f"({time.perf_counter() - t0:.1f}s)")
    return adj, L, ref


def span_attrs(tracer, name: str) -> dict:
    spans = [s for s in tracer.sink.spans() if s["name"] == name]
    check(len(spans) > 0, f"no {name} span recorded")
    return spans[-1].get("attrs", {})


def show_profile() -> None:
    from repro.core.planner import cost_model_token
    from repro.tuning import profile
    try:
        prof, exact = profile.lookup()
        found = (f"registry match for this device: {prof.name}" if exact
                 else f"no profile for this device; lookup() falls back to "
                      f"{prof.name} (platform {prof.backend.get('platform')})")
    except FileNotFoundError as e:
        found = f"no registry profile ({e})"
    log(f"[profile] active cost model: {profile.active_version()} "
        f"(planner token {cost_model_token()}); {found}")


def phase_planner(adj, L, ref: int) -> None:
    from repro.core import planner
    from repro.graphs import triangle_count
    t0 = time.perf_counter()
    p = planner.plan(L, L, L)
    plan_s = time.perf_counter() - t0
    executor = ("BCSR tile route" if p.algorithm == "tile"
                else "vmapped XLA row kernel")
    log(f"[planner] elected {p.algorithm} (executor: {executor}) in "
        f"{plan_s:.1f}s, trial among {list(p.trialed)}, "
        f"device limit {p.stats.mem_limit} bytes, modeled costs "
        f"{[(a, round(c)) for a, c in p.costs]}")
    t0 = time.perf_counter()
    count, spgemm_s = triangle_count(adj)
    wall = time.perf_counter() - t0
    log(f"[planner] triangles={count} reference={ref} "
        f"masked_spgemm {spgemm_s:.2f}s, wall {wall:.2f}s")
    check(count == ref, f"planner route counted {count}, reference {ref}")


def phase_tile(L, ref: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro import obs
    from repro.core import masked_spgemm
    from repro.kernels.masked_matmul import ops
    with obs.tracing() as tr:
        t0 = time.perf_counter()
        res = masked_spgemm(L, L, L, algorithm="tile", tile_block=TILE_BLOCK)
        count = exact_count(res)
        wall = time.perf_counter() - t0
    attrs = span_attrs(tr, "spgemm.tile")
    log(f"[tile] executor={attrs.get('executor')} "
        f"interpret={attrs.get('interpret')} block={attrs.get('block')}")
    check(attrs.get("executor") == "pallas"
          and attrs.get("interpret") is False,
          f"tile route did not run the compiled Pallas kernel: {attrs}")
    # the executor the span names lowers to a Mosaic kernel on this device
    blk = jax.ShapeDtypeStruct((8, TILE_BLOCK, TILE_BLOCK), jnp.float32)
    chunks = jax.ShapeDtypeStruct((1, 4, 16), jnp.int32)
    hlo = ops._block_spgemm_pallas.lower(
        blk, blk, chunks, nnzb_out=8, bs=TILE_BLOCK, interpret=False
    ).as_text()
    check("tpu_custom_call" in hlo, "Pallas executor has no Mosaic kernel")
    log(f"[tile] triangles={count} reference={ref} wall {wall:.2f}s "
        f"(first call: host prep + compile + run)")
    check(count == ref, f"tile route counted {count}, reference {ref}")


def serving_structure(n: int, deg: float, mask_deg: float, seed: int):
    from repro.core.formats import erdos_renyi
    return (erdos_renyi(n, deg, seed=seed), erdos_renyi(n, deg, seed=seed + 1),
            erdos_renyi(n, mask_deg, seed=seed + 2))


def reference_product(A, B, M):
    """float64 scipy ``M .* (A @ B)`` as a dense array (values > 0, so the
    structural pattern is where it is nonzero)."""
    import numpy as np
    import scipy.sparse as sp

    def s(x):
        return sp.csr_matrix((x.data.astype(np.float64), x.indices,
                              x.indptr), shape=x.shape)
    pattern = sp.csr_matrix((np.ones(M.nnz), M.indices, M.indptr),
                            shape=M.shape)
    return (s(A) @ s(B)).multiply(pattern).toarray()


def check_product(res, oneshot, ref, what: str) -> None:
    import numpy as np
    got = np.asarray(res.to_dense(), np.float64)
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=ATOL,
                               err_msg=f"{what} vs scipy reference")
    np.testing.assert_allclose(got, np.asarray(oneshot.to_dense()),
                               rtol=RTOL, atol=ATOL,
                               err_msg=f"{what} vs one-shot masked_spgemm")
    check(int(np.asarray(res.present).sum()) == int((ref != 0).sum()),
          f"{what}: present pattern differs from the reference")


def phase_serving(adj, ref: int) -> None:
    import numpy as np
    from repro.core import masked_spgemm
    from repro.core.formats import CSR
    from repro.serving import QueryEngine
    A1, B1, M1 = serving_structure(4096, 4, 128, seed=11)
    A2, B2, M2 = serving_structure(2048, 4, 64, seed=21)
    rng = np.random.default_rng(SEED)
    # one structure, three value sets: one bucket, served by the burst path
    A1s = [CSR(A1.indptr, A1.indices,
               rng.uniform(0.5, 1.0, A1.nnz).astype(np.float32), A1.shape)
           for _ in range(3)]
    t0 = time.perf_counter()
    with QueryEngine(max_batch=8) as eng:
        t1 = [eng.submit(a, B1, M1) for a in A1s]
        t2 = eng.submit(A2, B2, M2)
        tt = eng.submit_triangle(adj)
        out1 = [t.result() for t in t1]
        out2 = t2.result()
        tri = tt.result()
        log_rows = eng.metrics.bucket_log()
    wall = time.perf_counter() - t0
    for row in log_rows:
        log(f"[serve] bucket size={row['size']} route={row['route']} "
            f"algorithm={row['algorithm']} exec {row['exec_s']:.2f}s")
    check(any(r["route"] == "burst" and r["size"] == 3 for r in log_rows),
          "the repeated structure was not served by the burst path")
    for i, (a, res) in enumerate(zip(A1s, out1)):
        check_product(res, masked_spgemm(a, B1, M1),
                      reference_product(a, B1, M1), f"structure 1 query {i}")
    check_product(out2, masked_spgemm(A2, B2, M2),
                  reference_product(A2, B2, M2), "structure 2")
    log(f"[serve] 5 tickets: products match one-shot and scipy within "
        f"rtol={RTOL} atol={ATOL}; submit_triangle={tri} reference={ref}; "
        f"wall {wall:.2f}s")
    check(tri == ref, f"served triangle count {tri}, reference {ref}")


def phase_four_chips(big, small) -> None:
    """Ring route on the scale-16 graph against the single-chip tile route,
    row route on the ``ROW_SCALE`` graph against the single-chip planner
    route; both single-chip results come from device 0."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro import obs
    from repro.core import distributed_masked_spgemm, masked_spgemm
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("data",))
    for route, (_, L, ref) in (("ring", big), ("row", small)):
        t0 = time.perf_counter()
        if route == "ring":
            single = masked_spgemm(L, L, L, algorithm="tile",
                                   tile_block=TILE_BLOCK)
        else:
            single = masked_spgemm(L, L, L)
        single_count = exact_count(single)
        log(f"[single] device 0, {route} comparison: triangles="
            f"{single_count} ({time.perf_counter() - t0:.1f}s)")
        check(single_count == ref, f"single chip counted {single_count}")
        # host copies only: the ring program needs most of device 0's HBM
        want_present = np.asarray(single.present)
        want_vals = np.asarray(single.vals)
        del single
        with obs.tracing() as tr:
            t0 = time.perf_counter()
            out = distributed_masked_spgemm(
                L, L, L, mesh, algorithm=route,
                block_size=TILE_BLOCK if route == "ring" else None)
            count = exact_count(out)
            wall = time.perf_counter() - t0
        attrs = span_attrs(tr, "spgemm.dist")
        shards = out.vals.addressable_shards
        held = sorted({s.device.id for s in shards})
        log(f"[{route}] {attrs}; result shards on devices {held}, "
            f"rows per shard {[s.data.shape[0] for s in shards]}; "
            f"triangles={count} reference={ref} wall {wall:.2f}s")
        check(held == sorted(d.id for d in devs),
              f"{route} result is not spread over the four devices: {held}")
        if route == "ring":
            check(attrs.get("executor") == "pallas"
                  and attrs.get("interpret") is False,
                  f"ring did not run the compiled Pallas kernel: {attrs}")
        check(count == ref, f"{route} counted {count}, reference {ref}")
        check(np.array_equal(np.asarray(out.present), want_present)
              and np.array_equal(np.asarray(out.vals), want_vals),
              f"{route} result differs from the single-chip result")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    log(f"[mesh] peak bytes in use per device: {peaks}")
    check(all(p > 0 for p in peaks), "a device of the mesh was never used")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh path and its "
                         "single-chip comparison")
    args = ap.parse_args()
    device = device_info(4 if args.four_chips else 1)
    from repro import compile_cache
    log(f"[cache] compile cache at {compile_cache.enable()}")
    show_profile()
    t0 = time.perf_counter()
    big, small = build_graph(SCALE), build_graph(ROW_SCALE)
    if args.four_chips:
        phase_four_chips(big, small)
    else:
        phase_planner(*small)
        phase_tile(*big[1:])
        phase_serving(small[0], small[2])
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
