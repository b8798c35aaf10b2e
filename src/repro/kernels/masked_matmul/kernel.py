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
  time, §6's symbolic phase made free by the mask bound).  Its operands may
  carry a small plane axis, ``(nnzb, g, *block)``: one walk of the worklist
  multiplies every plane of an A block by the same plane of its B block
  (the tile route and the ring stack values in plane 0 and the stored-entry
  pattern in plane 1), so each grid step's fixed cost is paid once for all
  ``g`` products.

TPU notes: the grid is executed sequentially per core, so accumulating into
the same output block across consecutive grid steps (out index_map revisits)
is the canonical Mosaic reduction pattern.  Blocks are MXU-aligned; VMEM
footprint per step is bm*bk + bk*bn + bm*bn words.
"""
from __future__ import annotations

import functools
from typing import Tuple

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


def block_layout(bs: int) -> Tuple[int, int]:
    """``(rows, lanes)`` in which one ``bs x bs`` block is stored.

    The TPU lays out the last two dims of an f32 array in (8, 128) tiles,
    so a (32, 32) block takes a (32, 128) tile row: four times its bytes.
    Where a block's words fill whole tiles (bs 32 and 64) it can be stored
    lane-dense as ``(bs * bs // 128, 128)``; other sizes keep
    ``(bs, bs)``.  Element ``(r, c)`` of a block lies at
    ``(r % rows, (r // rows) * bs + c)`` (``block_position``).
    """
    if bs < 128 and (bs * bs) % (8 * 128) == 0:
        return bs * bs // 128, 128
    return bs, bs


def block_position(r, c, bs: int):
    """Where element ``(r, c)`` of a block lies in ``block_layout(bs)``."""
    rows, _ = block_layout(bs)
    return r % rows, (r // rows) * bs + c


def unpack_blocks(x, bs: int):
    """``(..., rows, lanes)`` stored blocks -> ``(..., bs, bs)``: the lane
    groups of width ``bs`` stacked along the rows (static slices only, so
    Mosaic compiles it inside a kernel)."""
    lanes = x.shape[-1]
    if lanes == bs:
        return x
    return jnp.concatenate([x[..., j * bs:(j + 1) * bs]
                            for j in range(lanes // bs)], axis=-2)


def pack_blocks(x, rows: int):
    """Inverse of ``unpack_blocks``: ``(..., bs, bs)`` -> stored blocks."""
    bs = x.shape[-1]
    if rows == bs:
        return x
    return jnp.concatenate([x[..., j * rows:(j + 1) * rows, :]
                            for j in range(bs // rows)], axis=-1)


def _block_spgemm_body(rank_ref, pa_ref, pb_ref, flags_ref,
                       a_ref, b_ref, carried_ref, o_ref, acc_ref, *,
                       bs, accumulate):
    w = pl.program_id(0)
    first = flags_ref[w] & 1
    real = (flags_ref[w] >> 1) & 1
    last = (flags_ref[w] >> 2) & 1
    planes = acc_ref.shape[0]

    @pl.when(first == 1)
    def _zero():
        if accumulate:
            for q in range(planes):
                acc_ref[q] = unpack_blocks(carried_ref[0, q], bs)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(real == 1)
    def _mac():
        for q in range(planes):
            acc_ref[q] += jnp.dot(unpack_blocks(a_ref[0, q], bs),
                                  unpack_blocks(b_ref[0, q], bs),
                                  preferred_element_type=jnp.float32)

    @pl.when(last == 1)
    def _flush():
        for q in range(planes):
            o_ref[0, q] = pack_blocks(acc_ref[q], o_ref.shape[2]).astype(
                o_ref.dtype)


def block_spgemm_kernel(a_blocks, b_blocks, rank, pa, pb, flags, out,
                        *, bs, interpret=False, accumulate=False):
    """Masked BCSR product from one worklist chunk, replayed into ``out``.

    a_blocks: (nnzb_a, g, *block); b_blocks: (nnzb_b, g, *block); out:
    (nnzb_out, g, *block) f32, where ``block`` is ``(bs, bs)`` or the
    lane-dense ``block_layout(bs)`` (the kernel unpacks each block it
    reads and packs each it writes).  ``g`` is the plane axis: one grid
    step reads an A block's ``g`` planes and a B block's as one block
    each and adds ``a[q] @ b[q]`` into plane ``q`` of the rank's
    accumulator, for every ``q``.  Operands without the axis,
    ``(nnzb, *block)``, are one plane, and so is the result.
    rank/pa/pb: (W,) int32 — output block rank and A/B block positions.
    flags: (W,) int32 bitfield — 1=first visit of rank, 2=real product
      (0 -> zero-fill entry for a mask block with no contribution),
      4=last visit of rank (flush accumulator to HBM); each holds for
      every plane alike.
    The worklist MUST be sorted by rank (sequential-grid accumulation) and
    must not end inside a rank.  ``out`` is aliased to the result: blocks
    the chunk does not visit keep their values, which is what lets
    ``ops.replay_chunks`` split a long worklist into calls whose
    scalar-prefetch arrays fit in SMEM.  With ``accumulate`` a rank's
    first visit starts from its block in ``out`` instead of zero, so a
    replay adds into ``out`` in place.
    """
    if out.ndim == 3:
        return block_spgemm_kernel(
            a_blocks[:, None], b_blocks[:, None], rank, pa, pb, flags,
            out[:, None], bs=bs, interpret=interpret,
            accumulate=accumulate)[:, 0]
    W = rank.shape[0]
    spec = functools.partial(pl.BlockSpec, (1,) + tuple(out.shape[1:]))
    at_rank = spec(lambda w, r_r, pa_r, pb_r, f_r: (r_r[w], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_block_spgemm_body, bs=bs, accumulate=accumulate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(W,),
            in_specs=[
                spec(lambda w, r_r, pa_r, pb_r, f_r: (pa_r[w], 0, 0, 0)),
                spec(lambda w, r_r, pa_r, pb_r, f_r: (pb_r[w], 0, 0, 0)),
                # ``out``: read only to accumulate
                at_rank if accumulate else pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=at_rank,
            scratch_shapes=[pltpu.VMEM((out.shape[1], bs, bs), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        # operand 6 = ``out`` (the four scalar-prefetch arrays count)
        input_output_aliases={6: 0},
        interpret=interpret,
    )(rank, pa, pb, flags, a_blocks, b_blocks, out)
