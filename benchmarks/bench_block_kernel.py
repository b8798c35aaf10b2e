"""Beyond-paper: the TPU-native tile path.

Two measurements (structural, CPU container):
1. masked tile kernels (interpret) vs jnp oracle — correctness + the tile
   worklist's flop saving vs a dense product (paper Fig. 1 at MXU scale).
2. block_masked vs dense_masked attention: XLA-compiled flop counts from
   cost_analysis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import bcsr_from_dense
from repro.kernels.masked_matmul.ops import block_spgemm, \
    build_spgemm_schedule
from repro.kernels.flash_mask.attention import (block_masked_attention,
                                                dense_masked_attention)
from .common import save


def flops_of(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca.get("flops", float("nan")))


def run():
    out = {}
    # --- tile worklist sizes: scheduled tiles vs dense tiles --------------
    # block-structured sparsity (tile-granular masks ARE block-structured:
    # attention/SSD masks switch whole MXU tiles on or off)
    rng = np.random.default_rng(0)
    n, bs = 512, 32
    nb = n // bs

    def block_sparse(dens, seed):
        r = np.random.default_rng(seed)
        tiles = (r.random((nb, nb)) < dens)
        return (np.kron(tiles, np.ones((bs, bs)))
                * r.standard_normal((n, n))).astype(np.float32)

    for dens in (0.05, 0.2, 0.5):
        A = block_sparse(dens, 1)
        B = block_sparse(dens, 2)
        M = (block_sparse(dens, 3) != 0).astype(np.float32)
        Ab, Bb, Mb = (bcsr_from_dense(A, bs), bcsr_from_dense(B, bs),
                      bcsr_from_dense(M, bs))
        rank, pa, pb, flags = build_spgemm_schedule(Ab, Bb, Mb)
        real = int((flags & 2).astype(bool).sum())
        dense_tiles = (n // bs) ** 3
        out[f"spgemm_dens{dens}"] = {
            "worklist_products": real,
            "dense_tile_products": dense_tiles,
            "flop_fraction": real / dense_tiles,
        }
        print(f"[block] density={dens}: {real}/{dense_tiles} tile products "
              f"({real / dense_tiles:.3f} of dense)", flush=True)

    # --- attention: compiled flops, block vs dense ------------------------
    b, h, s, d = 1, 2, 1024, 64
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16)
    for name, kw in [("causal", dict(causal=True)),
                     ("window256", dict(causal=True, window=256))]:
        f_dense = flops_of(lambda q_, k_, v_: dense_masked_attention(
            q_, k_, v_, **kw), q, q, q)
        f_block = flops_of(lambda q_, k_, v_: block_masked_attention(
            q_, k_, v_, bq=128, bk=128, **kw), q, q, q)
        out[f"attn_{name}"] = {"dense_flops": f_dense,
                               "block_flops": f_block,
                               "saving": 1 - f_block / f_dense}
        print(f"[block] attention {name}: dense={f_dense:.3e} "
              f"block={f_block:.3e} saving={1 - f_block / f_dense:.1%}",
              flush=True)
    save("block_kernel", out)
    return out


if __name__ == "__main__":
    run()
