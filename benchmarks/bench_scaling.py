"""Paper Fig. 11: strong scaling.

The paper scales OpenMP threads; the analogue here is devices: the
row-parallel masked SpGEMM under shard_map on 1/2/4/8 devices.  On an
accelerator host every count runs in this process, over the first n
devices.  On the CPU each count runs in a child interpreter with n forced
host devices (the device count locks at backend init); a child that fails
fails the run.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

from .common import save


def _measure(n: int) -> dict:
    """Median seconds of the row-parallel product over the first ``n``
    devices of this process."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed import pad_rows_to, row_parallel_masked_spgemm
    from repro.core.formats import (erdos_renyi, padded_from_csr,
                                    random_mask_like)

    g = erdos_renyi(4096, 16, seed=1)
    m = random_mask_like(g, 0.5, seed=2)
    A = padded_from_csr(g)
    B = padded_from_csr(g)
    M = padded_from_csr(m)
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    A, M = pad_rows_to(n, A, M)

    def go():
        vals, _ = row_parallel_masked_spgemm(A, B, M, mesh, algorithm="msa")
        vals.block_until_ready()

    go()
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        go()
        ts.append(time.perf_counter() - t0)
    return {"n": n, "seconds": float(np.median(ts))}


def _spawn(n: int) -> dict:
    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    r = subprocess.run([sys.executable, "-m", "benchmarks.bench_scaling",
                        "--child", str(n)], capture_output=True, text=True,
                       timeout=600, env=env, cwd=root)
    if r.returncode != 0:
        raise RuntimeError(f"bench_scaling child for {n} devices failed "
                           f"({r.returncode}): {r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run(device_counts=None):
    import jax
    from repro.tuning.probes import measure_in_process
    if device_counts is None:
        device_counts = tuple(
            n for n in (1, 2, 4, 8)
            if jax.default_backend() == "cpu" or n <= len(jax.devices()))
    out = {}
    for n in device_counts:
        d = _measure(n) if measure_in_process(n) else _spawn(n)
        out[str(n)] = d
        base = out.get("1", d)["seconds"]
        print(f"[scaling] devices={n} t={d['seconds']*1e3:.1f}ms "
              f"speedup={base / d['seconds']:.2f}x", flush=True)
    save("strong_scaling", out)
    return out


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        print(json.dumps(_measure(int(sys.argv[2]))))
    else:
        run()
