"""Jit'd wrappers + host-side schedule builders for the masked tile kernels.

The schedule builder is the TPU incarnation of the paper's symbolic phase:
because the mask's block structure bounds the output (paper §6, the 1P
insight), the output allocation and the worklist are fully determined on the
host before any device compute — so the device program is a single static
numeric phase.  The builder is pure vectorized numpy (segment ops over the
CSR structures); the per-block Python loops of the original demo would
dominate end-to-end time and defeat the point of a free symbolic phase.

Two executors replay the worklist:

* ``backend="pallas"`` — the Mosaic kernels in ``kernel.py`` (sequential
  grid, VMEM accumulator).  The real TPU path; ``interpret=True`` emulates
  it on CPU for tests.
* ``backend="xla"``    — gather + batched matmul + segment-sum, compiled by
  XLA.  The fast path on CPU/GPU where Pallas interpret mode would be pure
  Python overhead.

``backend=None`` picks pallas on TPU and xla elsewhere (``resolve_executor``),
re-queried per call (the backend can change mid-process, e.g. tests forcing
CPU after a TPU probe — caching the first answer forever ran compiled-mode
kernels in the wrong mode).  The Pallas executor replays long worklists in
chunks of ``SPGEMM_CHUNK`` entries, the most one kernel call's
scalar-prefetch arrays can hold in SMEM with room to spare.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import BCSR
from .kernel import masked_matmul_kernel, block_spgemm_kernel

Schedule = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


def on_tpu() -> bool:
    """Whether the *current* default backend is TPU (never cached here:
    ``jax.default_backend()`` is already memoized by jax and invalidated
    when the platform changes, so a module-global cache could only be
    stale, never faster)."""
    return jax.default_backend() == "tpu"


def tile_path_supported(semiring_name: str, complement: bool) -> bool:
    """Whether the tile kernels can express this product.

    Both executors accumulate with a dense MXU dot, so only the plus_times
    semiring is representable, and the mask must be explicit (a complement's
    output is not bounded by the mask's block structure).  The planner
    (``repro.core.planner``) consults this plus an occupancy estimate to set
    ``Plan.tile_eligible``.
    """
    return semiring_name == "plus_times" and not complement


def _check_tpu_tiling(a_shape, b_shape, bm: int, bn: int, bk: int) -> None:
    """Raise ``ValueError`` unless Mosaic can tile ``masked_matmul``'s blocks.

    The TPU tiles the last two dims of every block by (8, 128) f32 words,
    so each block dim must be a multiple of its tile or span the whole
    array dim: A's (bm, bk) block of (M, K) and B's (bk, bn) block of
    (K, N).  (The output's (bm, bn) block always spans its array dims.)
    """
    (M, K), (_, N) = a_shape, b_shape
    bad = [f"{name}={blk} (multiple of {tile} or {full} needed)"
           for name, blk, tile, full in (("bm", bm, 8, M), ("bk", bk, 8, K),
                                         ("bk", bk, 128, K),
                                         ("bn", bn, 128, N))
           if blk % tile and blk != full]
    if bad:
        raise ValueError("the TPU cannot tile these masked_matmul blocks: "
                         + ", ".join(bad))


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def masked_matmul(a, b, bi, bj, *, bm, bn, bk, interpret=None):
    """Tile-MCA SDDMM: only mask-allowed output tiles are computed.

    Compiled for the chip (``interpret=False``, the default on TPU), the
    block shape must be one the TPU can tile, or ``ValueError`` is raised
    before the compiler sees the kernel.
    """
    interpret = (not on_tpu()) if interpret is None else interpret
    if not interpret:
        _check_tpu_tiling(a.shape, b.shape, bm, bn, bk)
    return masked_matmul_kernel(a, b, bi, bj, bm=bm, bn=bn, bk=bk,
                                interpret=interpret)


# ---------------------------------------------------------------------------
# BCSR x BCSR schedule (host, vectorized)
# ---------------------------------------------------------------------------


def _empty_schedule() -> Schedule:
    z = np.zeros(0, np.int32)
    return z, z.copy(), z.copy(), z.copy()


def build_spgemm_schedule(A: BCSR, B: BCSR, M: BCSR) -> Schedule:
    """Worklist (rank, posA, posB, flags) for C = M (.) (A B) on block
    structures.

    For every mask block (i, j) [rank r in M's CSR order], the worklist
    holds one entry per block k with A[i, k] and B[k, j] both present, in
    ascending k; mask blocks with no contribution get a single zero-fill
    entry (flags real-bit = 0) so the kernel's output is fully defined.
    ``flags`` bits: 1 = first visit of rank, 2 = real product, 4 = last
    visit of rank.

    Implementation is pure vectorized numpy: the candidate set (every
    (mask block, A block) pair sharing a block row) is expanded with
    segment ops, then matched against B's column-major structure with one
    searchsorted over composite (block-col, block-row) keys.  Work and
    memory are O(sum over mask blocks of nnzb(A block-row)) — the same
    asymptotics the per-block Python loop had, minus the interpreter.
    """
    if M.nnzb == 0:
        return _empty_schedule()

    from repro.core.formats import bcsr_structure_transpose
    bt_indptr, bt_rows, bt_pos = bcsr_structure_transpose(B)

    nnzb_m = M.nnzb
    mi = np.repeat(np.arange(M.block_rows, dtype=np.int64),
                   np.diff(M.indptr))                  # mask block-row per rank
    mj = M.indices                                     # mask block-col per rank

    # expand: one candidate per (rank, A block in block-row mi[rank])
    a_cnt = np.diff(A.indptr)
    counts = a_cnt[mi]
    total = int(counts.sum())
    rep_r = np.repeat(np.arange(nnzb_m, dtype=np.int64), counts)
    starts = np.zeros(nnzb_m, dtype=np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    within = np.arange(total, dtype=np.int64) - np.repeat(starts, counts)
    a_pos = A.indptr[mi[rep_r]] + within
    k = A.indices[a_pos]

    # match candidates against B's column-major structure: bt is sorted by
    # (block-col, block-row), so composite keys are globally sorted and one
    # searchsorted resolves every candidate
    kb = B.block_rows
    bt_cols = np.repeat(np.arange(B.block_cols, dtype=np.int64),
                        np.diff(bt_indptr))
    bt_key = bt_cols * kb + bt_rows
    cand_key = mj[rep_r] * kb + k
    if len(bt_key):
        pos = np.searchsorted(bt_key, cand_key)
        pos_c = np.minimum(pos, len(bt_key) - 1)
        hit = (pos < len(bt_key)) & (bt_key[pos_c] == cand_key)
    else:
        hit = np.zeros(total, dtype=bool)

    rank = rep_r[hit]                 # nondecreasing: rep_r was, filter keeps
    pa = a_pos[hit]
    pb = bt_pos[np.minimum(pos[hit], max(0, len(bt_key) - 1))] \
        if len(bt_key) else np.zeros(0, np.int64)
    real = np.ones(len(rank), dtype=np.int32)

    # zero-fill entries for mask blocks with no contribution
    per_rank = np.bincount(rank, minlength=nnzb_m)
    empty = np.nonzero(per_rank == 0)[0]
    if len(empty):
        rank = np.concatenate([rank, empty])
        pa = np.concatenate([pa, np.zeros(len(empty), np.int64)])
        pb = np.concatenate([pb, np.zeros(len(empty), np.int64)])
        real = np.concatenate([real, np.zeros(len(empty), np.int32)])
        order = np.argsort(rank, kind="stable")
        rank, pa, pb, real = rank[order], pa[order], pb[order], real[order]

    first = np.empty(len(rank), dtype=bool)
    first[:1] = True
    np.not_equal(rank[1:], rank[:-1], out=first[1:])
    last = np.empty(len(rank), dtype=bool)
    last[-1:] = True
    np.not_equal(rank[1:], rank[:-1], out=last[:-1])
    flags = first * 1 + real * 2 + last * 4
    return (rank.astype(np.int32), pa.astype(np.int32),
            pb.astype(np.int32), flags.astype(np.int32))


# ---------------------------------------------------------------------------
# K-slab schedules (distributed ring-SUMMA): one worklist per ring stage
# ---------------------------------------------------------------------------


def build_spgemm_schedule_slab(A: BCSR, B_slab: BCSR, M: BCSR,
                               k0_blocks: int) -> Schedule:
    """Worklist for C = M (.) (A[:, slab] @ B_slab), one ring stage.

    ``B_slab`` holds block rows [k0_blocks, k0_blocks + B_slab.block_rows)
    of the full B, rebased to start at 0 (its ``pb`` positions index the
    slab's own blocks).  ``pa`` positions index the full panel ``A.blocks``.
    Zero-fill semantics match ``build_spgemm_schedule``: every mask block
    gets at least one entry, so a per-stage executor's output is fully
    defined even for stages whose slab contributes nothing.
    """
    rows_slab = B_slab.block_rows
    in_slab = (A.indices >= k0_blocks) & (A.indices < k0_blocks + rows_slab)
    pos_map = np.nonzero(in_slab)[0]
    brow = np.repeat(np.arange(A.block_rows, dtype=np.int64),
                     np.diff(A.indptr))[in_slab]
    indptr_sub = np.zeros(A.block_rows + 1, dtype=np.int64)
    np.add.at(indptr_sub, brow + 1, 1)
    A_sub = BCSR(np.cumsum(indptr_sub), A.indices[in_slab] - k0_blocks,
                 A.blocks, (A.shape[0], rows_slab * A.block_size),
                 A.block_size)
    rank, pa, pb, flags = build_spgemm_schedule(A_sub, B_slab, M)
    # remap pa from slab-filtered positions back to the full panel's blocks
    # (zero-fill entries keep position 0 — they never contribute)
    real = (flags >> 1) & 1
    if len(pos_map):
        pa = np.where(real == 1, pos_map[np.minimum(pa, len(pos_map) - 1)],
                      0).astype(np.int32)
    else:
        pa = np.zeros_like(pa)
    return rank, pa, pb, flags


def build_ring_schedules(A_panels, B_slabs, M_panels, *, out_pad: int
                         ) -> np.ndarray:
    """Stacked per-device, per-stage chunked worklists for the sparse ring.

    Returns int32 ``(p, p, n_chunks, 4, length)``: ``[d, s]`` holds the
    worklist ``(rank, pa, pb, flags)`` device ``d`` replays at ring stage
    ``s``, when it holds B K-slab ``(d - s) % p``, split by
    ``chunk_schedule`` into chunks of one static length.  Ranks
    ``[nnzb(M_panel), out_pad)`` (the ring-wide output padding) get
    zero-fill entries (flags first|last, real off), so every output rank
    of every stage is written.  Stages with fewer chunks than the ring's
    longest are padded with chunks whose flags are all off, which
    ``replay_chunks`` never launches.
    """
    p = len(A_panels)
    assert len(B_slabs) == len(M_panels) == p
    slab_rows = B_slabs[0].block_rows
    scheds = {}
    for d in range(p):
        for s in range(p):
            src = (d - s) % p
            rank, pa, pb, flags = build_spgemm_schedule_slab(
                A_panels[d], B_slabs[src], M_panels[d], src * slab_rows)
            nloc = M_panels[d].nnzb
            if out_pad > nloc:
                extra = np.arange(nloc, out_pad, dtype=np.int32)
                z = np.zeros(len(extra), np.int32)
                rank = np.concatenate([rank, extra])
                pa = np.concatenate([pa, z])
                pb = np.concatenate([pb, z])
                flags = np.concatenate([flags, np.full(len(extra), 5,
                                                       np.int32)])
            scheds[d, s] = (rank, pa, pb, flags)
    length = min(SPGEMM_CHUNK, max(len(w[0]) for w in scheds.values()))
    chunked = {ds: chunk_schedule(w, length) for ds, w in scheds.items()}
    n_chunks = max(len(c) for c in chunked.values())
    out = np.zeros((p, p, n_chunks, 4, length), np.int32)
    for (d, s), c in chunked.items():
        out[d, s, :len(c)] = c
    return out


# ---------------------------------------------------------------------------
# Worklist executors
# ---------------------------------------------------------------------------


#: worklist entries one ``block_spgemm_kernel`` call replays.  The call's
#: four scalar-prefetch arrays take 16 bytes per entry of the TPU core's
#: 1 MiB SMEM: on v5e a 65,536-entry call fails to compile (out of SMEM)
#: and 60,000 entries compile.  16,384 entries (256 KiB) leave three
#: quarters of SMEM to Mosaic, and one chunk is ~16k grid steps, so the
#: per-call launch cost stays negligible.
SPGEMM_CHUNK = 16384


def chunk_schedule(schedule: Schedule, length: int) -> np.ndarray:
    """Split a rank-sorted worklist into int32 ``(n_chunks, 4, length)``.

    Chunks end only at rank boundaries, so every rank's first and last
    visit stay inside one kernel call and the flags keep their meaning.
    The tail of each chunk repeats its last rank with all flags off: those
    steps neither zero, accumulate nor flush, and the block they revisit
    is the one the chunk just flushed.  Raises ``ValueError`` when one
    rank alone has more than ``length`` entries.
    """
    rank = schedule[0]
    W = len(rank)
    starts = np.concatenate([[0], np.flatnonzero(np.diff(rank)) + 1])
    cuts = [0]
    while cuts[-1] + length < W:
        i = np.searchsorted(starts, cuts[-1] + length, side="right") - 1
        if starts[i] <= cuts[-1]:
            raise ValueError(
                f"output block rank {int(rank[cuts[-1]])} has more than "
                f"{length} worklist entries; it cannot fit in one chunk")
        cuts.append(int(starts[i]))
    cuts = np.asarray(cuts + [W])
    lens = np.diff(cuts)
    out = np.zeros((len(lens), 4, length), np.int32)
    out[:, 0, :] = rank[cuts[1:] - 1][:, None]
    chunk_of = np.repeat(np.arange(len(lens)), lens)
    pos = np.arange(W) - np.repeat(cuts[:-1], lens)
    for i, arr in enumerate(schedule):
        out[chunk_of, i, pos] = arr
    return out


def replay_chunks(out, a_blocks, b_blocks, chunks, *, bs, interpret):
    """Replay a chunked worklist (``chunk_schedule``) into ``out`` on the
    Pallas kernel, one call per chunk, each call aliasing ``out`` so the
    blocks it does not visit survive.  Chunks whose flags are all off
    (the ring's stage padding) are never launched: a Pallas output block
    is written back on every visit, even one whose body never stored."""
    n_real = jnp.sum(jnp.any(chunks[:, 3, :] != 0, axis=1))

    def body(c, out):
        rank, pa, pb, flags = (chunks[c, i] for i in range(4))
        return block_spgemm_kernel(a_blocks, b_blocks, rank, pa, pb, flags,
                                   out, bs=bs, interpret=interpret)

    return jax.lax.fori_loop(0, n_real, body, out)


@functools.partial(jax.jit,
                   static_argnames=("nnzb_out", "bs", "interpret"))
def _block_spgemm_pallas(a_blocks, b_blocks, chunks, *, nnzb_out, bs,
                         interpret):
    out = jnp.zeros((nnzb_out, bs, bs), jnp.float32)
    return replay_chunks(out, a_blocks, b_blocks, chunks, bs=bs,
                         interpret=interpret)


@jax.jit
def _xla_chunk_add(out, a_blocks, b_blocks, rank, pa, pb, flags):
    """One worklist chunk: gather, batched matmul, segment-add into ``out``.
    Zero-fill entries (real-bit off) gather block 0 but contribute
    nothing."""
    real = ((flags >> 1) & 1).astype(jnp.float32)
    prods = jnp.einsum("wij,wjk->wik",
                       a_blocks[pa].astype(jnp.float32),
                       b_blocks[pb].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    return out.at[rank].add(prods * real[:, None, None])


#: peak f32 elements the XLA executor materializes per worklist chunk
#: (~64 MB); bounds device memory at O(chunk * bs^2) instead of O(W * bs^2)
#: for huge worklists, where one unchunked einsum could out-allocate the
#: very densify this pipeline removed
_XLA_CHUNK_ELEMS = 1 << 24


def _block_spgemm_xla(a_blocks, b_blocks, rank, pa, pb, flags, *,
                      nnzb_out, bs):
    """XLA replay of the worklist, chunked to bound peak memory.

    Chunks are independent partial sums into the same output (the rank
    segment-add is associative), so first/last flags are irrelevant here —
    only the real-bit is consulted.  The tail chunk is padded with
    real-bit-off entries to keep exactly one compiled chunk shape.
    """
    W = int(rank.shape[0])
    chunk = max(1, _XLA_CHUNK_ELEMS // (bs * bs))
    out = jnp.zeros((nnzb_out, bs, bs), jnp.float32)
    if W <= chunk:
        return _xla_chunk_add(out, a_blocks, b_blocks, rank, pa, pb, flags)
    pad = -W % chunk
    if pad:
        z = jnp.zeros(pad, rank.dtype)
        rank, pa, pb = (jnp.concatenate([x, z]) for x in (rank, pa, pb))
        flags = jnp.concatenate([flags, z])
    for s in range(0, W + pad, chunk):
        e = s + chunk
        out = _xla_chunk_add(out, a_blocks, b_blocks, rank[s:e], pa[s:e],
                             pb[s:e], flags[s:e])
    return out


def resolve_executor(backend: Optional[str], interpret: Optional[bool]
                     ) -> Tuple[str, Optional[bool]]:
    """The worklist executor a call runs on: ``(backend, interpret)``.

    ``backend=None`` picks ``"pallas"`` on TPU and ``"xla"`` elsewhere;
    ``interpret=True`` requests the Pallas kernel in interpret mode (the
    CPU tests), while ``interpret=False`` only means "compiled mode if
    Pallas runs at all" — off the TPU it still picks XLA, never
    compiled-mode Mosaic on a host platform.  ``interpret`` comes back
    resolved for Pallas and ``None`` for XLA.  Callers record the pair on
    their span, so a trace shows which executor ran.
    """
    if backend is None:
        backend = "pallas" if (interpret or on_tpu()) else "xla"
    if backend == "pallas":
        return backend, (not on_tpu()) if interpret is None else interpret
    if backend == "xla":
        return backend, None
    raise ValueError(f"unknown backend {backend!r}")


def _run_schedule(A: BCSR, B: BCSR, M: BCSR, schedule: Schedule,
                  blocks_a, blocks_b, *, interpret, backend):
    bs = A.block_size
    backend, interpret = resolve_executor(backend, interpret)
    # an empty operand leaves only zero-fill entries in the worklist, but
    # those still address block 0 — give them one zero block to read
    if blocks_a.shape[0] == 0:
        blocks_a = jnp.zeros((1, bs, bs), blocks_a.dtype)
    if blocks_b.shape[0] == 0:
        blocks_b = jnp.zeros((1, bs, bs), blocks_b.dtype)
    if backend == "pallas":
        chunks = chunk_schedule(schedule,
                                min(SPGEMM_CHUNK, len(schedule[0])))
        return _block_spgemm_pallas(blocks_a, blocks_b, jnp.asarray(chunks),
                                    nnzb_out=M.nnzb, bs=bs,
                                    interpret=interpret)
    rank, pa, pb, flags = (jnp.asarray(x) for x in schedule)
    return _block_spgemm_xla(blocks_a, blocks_b, rank, pa, pb, flags,
                             nnzb_out=M.nnzb, bs=bs)


def block_spgemm(A: BCSR, B: BCSR, M: BCSR, *, interpret=None,
                 backend: Optional[str] = None,
                 schedule: Optional[Schedule] = None) -> BCSR:
    """C = M (.) (A B) at tile granularity.  Output structure == M structure
    (the 1P allocation); zero blocks are kept (callers may prune via
    ``bcsr_to_csr``).

    An all-empty mask is a defined degenerate case: the worklist is empty
    and an empty BCSR is returned without launching a kernel.  Pass a
    precomputed ``schedule`` to amortize the symbolic phase across several
    numeric replays (e.g. a values pass and a structure pass).
    """
    assert A.block_size == B.block_size == M.block_size
    bs = A.block_size
    if M.nnzb == 0:
        return BCSR(M.indptr.copy(), M.indices.copy(),
                    jnp.zeros((0, bs, bs), jnp.float32),
                    (M.shape[0], B.shape[1]), bs)
    if schedule is None:
        schedule = build_spgemm_schedule(A, B, M)
    blocks = _run_schedule(A, B, M, schedule, A.blocks, B.blocks,
                           interpret=interpret, backend=backend)
    return BCSR(M.indptr.copy(), M.indices.copy(), blocks,
                (M.shape[0], B.shape[1]), bs)


def block_spgemm_with_structure(A: BCSR, B: BCSR, M: BCSR, *,
                                a_pattern=None, b_pattern=None,
                                interpret=None,
                                backend: Optional[str] = None
                                ) -> Tuple[BCSR, BCSR]:
    """(values, structural-counts) pair sharing ONE schedule build.

    The second BCSR replays the same worklist over the operands' 0/1
    patterns; its entries count structural contributions, so ``count > 0``
    is exact element-level presence — identical to the row kernels'
    structural semantics even when numeric cancellation produces a stored
    0.0 in the values pass.  ``a_pattern``/``b_pattern`` are optional
    (nnzb, bs, bs) 0/1 block arrays marking the operands' *stored entries*
    (the row kernels treat an explicitly stored 0.0 as structural); when
    omitted, value-nonzeroness of the blocks is used, which cannot tell a
    stored zero from block padding.
    """
    assert A.block_size == B.block_size == M.block_size
    bs = A.block_size
    shape = (M.shape[0], B.shape[1])
    if M.nnzb == 0:
        empty = jnp.zeros((0, bs, bs), jnp.float32)
        return (BCSR(M.indptr.copy(), M.indices.copy(), empty, shape, bs),
                BCSR(M.indptr.copy(), M.indices.copy(), empty, shape, bs))
    schedule = build_spgemm_schedule(A, B, M)
    vals = _run_schedule(A, B, M, schedule, A.blocks, B.blocks,
                         interpret=interpret, backend=backend)
    if a_pattern is None:
        a_pattern = (A.blocks != 0).astype(jnp.float32)
    if b_pattern is None:
        b_pattern = (B.blocks != 0).astype(jnp.float32)
    struct = _run_schedule(A, B, M, schedule, a_pattern, b_pattern,
                           interpret=interpret, backend=backend)
    return (BCSR(M.indptr.copy(), M.indices.copy(), vals, shape, bs),
            BCSR(M.indptr.copy(), M.indices.copy(), struct, shape, bs))


def block_spgemm_from_csr(A, B, M, *, block_size: int, interpret=None,
                          backend: Optional[str] = None) -> BCSR:
    """Tile path from host CSR operands (the ``Plan.tile_eligible`` route).

    Densify-free: operands are scattered straight into their occupied
    blocks (``bcsr_from_csr``), so memory stays O(occupied blocks) instead
    of O(m*n) — the property that makes this route usable at scales where
    the original demo's ``to_dense`` re-blocking could not run.
    """
    from repro.core.formats import bcsr_from_csr
    Ab = bcsr_from_csr(A, block_size)
    Bb = bcsr_from_csr(B, block_size)
    Mb = bcsr_from_csr(M, block_size)
    return block_spgemm(Ab, Bb, Mb, interpret=interpret, backend=backend)
