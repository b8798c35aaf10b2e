"""Pallas TPU kernels for masked tile products (the paper's technique at MXU
granularity).

Two kernels:

* ``masked_matmul_kernel`` — tile-MCA SDDMM: dense A (M,K) x dense B (K,N),
  computing ONLY the output tiles allowed by the mask's block structure.
  The accumulator is exactly the paper's MCA: its length is nnzb(M) tiles,
  indexed by mask-block *rank* (the output array's leading dim), and only the
  states ALLOWED (tile scheduled) / SET (tile computed) exist.  NOTALLOWED
  tiles are never even scheduled — the paper's "skip masked-out flops".

* ``block_spgemm_kernel`` — BCSR x BCSR masked product replaying a host-built
  worklist (the paper's Heap merge performed once at schedule-construction
  time, §6's symbolic phase made free by the mask bound).

TPU notes: the grid is executed sequentially per core, so accumulating into
the same output block across consecutive grid steps (out index_map revisits)
is the canonical Mosaic reduction pattern.  Blocks are MXU-aligned; VMEM
footprint per step is bm*bk + bk*bn + bm*bn words.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Tile-MCA SDDMM:  C[r] = A[bi[r], :] @ B[:, bj[r]]   for each mask block r
# ---------------------------------------------------------------------------


def _masked_matmul_body(bi_ref, bj_ref, a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)[None]


def masked_matmul_kernel(a, b, bi, bj, *, bm, bn, bk, out_dtype=jnp.float32,
                         interpret=False):
    """C_tiles[r] = (A @ B) tile (bi[r], bj[r]); only allowed tiles computed.

    a: (M, K), b: (K, N); M % bm == 0, N % bn == 0, K % bk == 0.
    bi, bj: (nnzb,) int32 mask block coordinates.
    Returns (nnzb, bm, bn) out_dtype.
    """
    nnzb = bi.shape[0]
    K = a.shape[1]
    grid = (nnzb, K // bk)
    return pl.pallas_call(
        _masked_matmul_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda r, k, bi_r, bj_r: (bi_r[r], k)),
                pl.BlockSpec((bk, bn), lambda r, k, bi_r, bj_r: (k, bj_r[r])),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda r, k, bi_r, bj_r: (r, 0, 0)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nnzb, bm, bn), out_dtype),
        interpret=interpret,
    )(bi, bj, a, b)


# ---------------------------------------------------------------------------
# BCSR x BCSR masked SpGEMM: replay a host-built (rank, posA, posB) worklist
# ---------------------------------------------------------------------------


def _block_spgemm_body(rank_ref, pa_ref, pb_ref, flags_ref,
                       a_ref, b_ref, _carried_ref, o_ref, acc_ref):
    w = pl.program_id(0)
    first = flags_ref[w] & 1
    real = (flags_ref[w] >> 1) & 1
    last = (flags_ref[w] >> 2) & 1

    @pl.when(first == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(real == 1)
    def _mac():
        acc_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(last == 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)[None]


def block_spgemm_kernel(a_blocks, b_blocks, rank, pa, pb, flags, out,
                        *, bs, interpret=False):
    """Masked BCSR product from one worklist chunk, replayed into ``out``.

    a_blocks: (nnzb_a, bs, bs); b_blocks: (nnzb_b, bs, bs).
    rank/pa/pb: (W,) int32 — output block rank and A/B block positions.
    flags: (W,) int32 bitfield — 1=first visit of rank, 2=real product
      (0 -> zero-fill entry for a mask block with no contribution),
      4=last visit of rank (flush accumulator to HBM).
    The worklist MUST be sorted by rank (sequential-grid accumulation) and
    must not end inside a rank.  ``out`` (nnzb_out, bs, bs) f32 is aliased
    to the result: blocks the chunk does not visit keep their values, which
    is what lets ``ops.replay_chunks`` split a long worklist into calls
    whose scalar-prefetch arrays fit in SMEM.
    """
    W = rank.shape[0]
    return pl.pallas_call(
        _block_spgemm_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(W,),
            in_specs=[
                pl.BlockSpec((1, bs, bs),
                             lambda w, r_r, pa_r, pb_r, f_r: (pa_r[w], 0, 0)),
                pl.BlockSpec((1, bs, bs),
                             lambda w, r_r, pa_r, pb_r, f_r: (pb_r[w], 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # aliased, never read
            ],
            out_specs=pl.BlockSpec((1, bs, bs),
                                   lambda w, r_r, pa_r, pb_r, f_r:
                                   (r_r[w], 0, 0)),
            scratch_shapes=[pltpu.VMEM((bs, bs), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        # operand 6 = ``out`` (the four scalar-prefetch arrays count)
        input_output_aliases={6: 0},
        interpret=interpret,
    )(rank, pa, pb, flags, a_blocks, b_blocks, out)
