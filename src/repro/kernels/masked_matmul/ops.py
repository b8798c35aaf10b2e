"""Jit'd wrappers + host-side schedule builders for the masked tile kernels.

The schedule builder is the TPU incarnation of the paper's symbolic phase:
because the mask's block structure bounds the output (paper §6, the 1P
insight), the output allocation and the worklist are fully determined on the
host before any device compute — so the device program is a single static
numeric phase.  The builder is pure vectorized numpy (segment ops over the
CSR structures); the per-block Python loops of the original demo would
dominate end-to-end time and defeat the point of a free symbolic phase.
It expands, for each mask block (i, j), only the blocks of A's block row
i that fall inside the block rows B's column j spans, and resolves each
such candidate by a direct lookup in a dense int32 table of B's block
positions where that table is no larger than the candidate list (else by
one searchsorted over B's column-major keys).

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
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.formats import BCSR, to_device
from .kernel import (block_spgemm_kernel, masked_matmul_kernel, pack_blocks,
                     unpack_blocks)

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

    Implementation is pure vectorized numpy.  B's block column j holds
    blocks only in block rows ``[bmin[j], bmax[j]]``, so the candidates of
    mask block (i, j) are the blocks of A's block row i inside that range:
    two searchsorted calls over A's (block-row, block-col) keys, one pair
    per mask block, bound them, and a block of A outside it is never
    expanded.  Each candidate is then resolved against B: by a direct
    lookup in a dense int32 table of B's block positions (plus one, 0 for
    absent) over ``block_cols x block_rows`` when the table has no more
    slots than there are candidates, so it costs no more memory than one
    candidate array (4 bytes a slot: 16.8 MB for a 2,048-block-square
    grid); otherwise by one searchsorted over B's column-major composite
    (block-col, block-row) keys.  The choice follows from the operands'
    sizes alone.  Zero-fill entries are placed by a prefix sum over each
    rank's entry count (at least one), not by a sort.  Work and memory
    are O(candidates + mask blocks + the table).

    Runs under an ``spgemm.schedule`` span with the worklist's
    ``entries``, the ``candidates`` expanded (``entries`` less the
    zero-fill entries, over ``candidates``, is the share that hit) and
    the ``lookup`` that resolved them, ``"table"`` or ``"search"``.
    """
    with obs.span("spgemm.schedule") as sp:
        schedule, candidates, lookup = _spgemm_schedule(A, B, M)
        sp.set(entries=len(schedule[0]), candidates=candidates,
               lookup=lookup)
    return schedule


def _spgemm_schedule(A: BCSR, B: BCSR, M: BCSR
                     ) -> Tuple[Schedule, int, str]:
    """``(schedule, candidates, lookup)``: see ``build_spgemm_schedule``."""
    if M.nnzb == 0:
        return _empty_schedule(), 0, "search"

    from repro.core.formats import bcsr_structure_transpose
    bt_indptr, bt_rows, bt_pos = bcsr_structure_transpose(B)
    kb = B.block_rows
    ka = A.block_cols
    nnzb_m = M.nnzb
    mi = np.repeat(np.arange(M.block_rows, dtype=np.int64),
                   np.diff(M.indptr))                  # mask block-row per rank
    mj = M.indices.astype(np.int64, copy=False)        # mask block-col per rank

    # the block rows B's column j spans: [bmin[j], bmax[j]], empty (bmin >
    # bmax) for an empty column; bt is sorted by block row within a column
    bmin = np.ones(B.block_cols, np.int64)
    bmax = np.zeros(B.block_cols, np.int64)
    full = np.flatnonzero(np.diff(bt_indptr))
    bmin[full] = bt_rows[bt_indptr[full]]
    bmax[full] = np.minimum(bt_rows[bt_indptr[full + 1] - 1], ka - 1)

    # A's blocks of block row mi[r] inside that range: [lo[r], hi[r])
    a_rows = np.repeat(np.arange(A.block_rows, dtype=np.int64),
                       np.diff(A.indptr))
    a_key = a_rows * ka + A.indices
    row_key = mi * ka
    lo = np.searchsorted(a_key, row_key + bmin[mj])
    hi = np.searchsorted(a_key, row_key + bmax[mj], side="right")
    counts = np.maximum(hi - lo, 0)
    total = int(counts.sum())
    ends = np.cumsum(counts)

    # expand: one candidate per (rank, A block in its range), rank-major
    it = np.int32 if max(total, A.nnzb) < 2 ** 31 else np.int64
    a_pos = np.arange(total, dtype=it)
    a_pos += np.repeat((lo - (ends - counts)).astype(it), counts)
    cand_key = np.repeat(mj * kb, counts)              # int64 (col, row) keys
    cand_key += A.indices[a_pos]

    lookup = "table" if B.block_cols * kb <= total else "search"
    if lookup == "table":
        b_rows = np.repeat(np.arange(kb, dtype=np.int64), np.diff(B.indptr))
        table = np.zeros(B.block_cols * kb, np.int32)  # position + 1
        table[B.indices * kb + b_rows] = np.arange(1, B.nnzb + 1,
                                                   dtype=np.int32)
        found = table[cand_key]
        hit = found != 0
        pb = found[hit] - 1
    else:
        # bt is sorted by (block-col, block-row): the composite keys are
        # globally sorted and one searchsorted resolves every candidate
        bt_cols = np.repeat(np.arange(B.block_cols, dtype=np.int64),
                            np.diff(bt_indptr))
        bt_key = bt_cols * kb + bt_rows
        pos = np.minimum(np.searchsorted(bt_key, cand_key), len(bt_key) - 1)
        hit = bt_key[pos] == cand_key
        pb = bt_pos[pos[hit]].astype(np.int32)
    pa = a_pos[hit].astype(np.int32, copy=False)

    # entries per rank, summed over the ranks with candidates (their
    # segments of `hit` are contiguous and in order)
    per_rank = np.zeros(nnzb_m, np.int64)
    some = np.flatnonzero(counts)
    per_rank[some] = np.add.reduceat(hit, ends[some] - counts[some],
                                     dtype=np.int32)
    # a rank with none takes one zero-fill entry, where the prefix sum
    # over max(entries, 1) puts it: after the real entries of the ranks
    # before it
    slots = np.maximum(per_rank, 1)
    rank = np.repeat(np.arange(nnzb_m, dtype=np.int32), slots)
    empty = np.flatnonzero(per_rank == 0)
    if len(empty):
        before = (np.cumsum(per_rank) - per_rank)[empty]
        pa = np.insert(pa, before, 0)
        pb = np.insert(pb, before, 0)

    end = np.cumsum(slots)
    first = end - slots
    flags = np.full(len(rank), 2, np.int32)
    flags[first[empty]] = 0
    flags[first] += 1
    flags[end - 1] += 4
    return (rank, pa, pb, flags), total, lookup


# ---------------------------------------------------------------------------
# Ring schedules (distributed ring-SUMMA): one worklist per device and stage
# ---------------------------------------------------------------------------


def block_devices(indptr: np.ndarray, owner: np.ndarray) -> np.ndarray:
    """The device holding each block (CSR order) of a block structure
    whose block row ``i`` lies on device ``owner[i]``."""
    return np.repeat(np.asarray(owner, np.int64), np.diff(indptr))


def local_positions(device: np.ndarray, p: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """``(local, counts)`` for items dealt to ``p`` devices: item ``i``
    lies on ``device[i]`` at position ``local[i]`` of that device's items,
    which keep their order; ``counts[d]`` items lie on device ``d``."""
    counts = np.bincount(device, minlength=p)
    order = np.argsort(device, kind="stable")
    local = np.empty(len(device), np.int64)
    local[order] = (np.arange(len(device))
                    - np.repeat(np.cumsum(counts) - counts, counts))
    return local, counts


def build_ring_schedules(A: BCSR, B: BCSR, M: BCSR, owner: np.ndarray,
                         k_owner: np.ndarray, p: int
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-device, per-stage chunked worklists for the sparse ring.

    Device ``d`` holds the block rows ``owner == d`` of A and M (its row
    panel) and, at ring stage ``s``, B's K-slab ``(d - s) % p``: the
    block rows ``k_owner == (d - s) % p`` of B.  The full worklist of
    ``build_spgemm_schedule`` is split by (device, stage) and its
    positions made local to the panel and the slab, so each stage's
    entries stay sorted by rank.  Only real products are kept: the ring
    replays with ``accumulate``, where a rank no entry visits keeps its
    value, so no zero-fill entries are needed.

    Returns ``(chunks, entries)``: int32 ``(p, p, n_chunks, 4, length)``,
    where ``[d, s]`` is device ``d``'s worklist at stage ``s`` split by
    ``chunk_schedule`` into chunks of one static length (stages with
    fewer chunks than the longest are padded with chunks whose flags are
    all off, which ``replay_chunks`` never launches), and ``(p, p)``
    products per device and stage.
    """
    rank, pa, pb, flags = build_spgemm_schedule(A, B, M)
    real = ((flags >> 1) & 1) == 1
    rank, pa, pb = rank[real], pa[real], pb[real]
    m_dev = block_devices(M.indptr, owner)
    b_dev = block_devices(B.indptr, k_owner)
    m_loc, _ = local_positions(m_dev, p)
    a_loc, _ = local_positions(block_devices(A.indptr, owner), p)
    b_loc, _ = local_positions(b_dev, p)
    dev = m_dev[rank]
    group = dev * p + (dev - b_dev[pb]) % p          # device, stage
    order = np.argsort(group, kind="stable")
    group = group[order]
    rank, pa, pb = m_loc[rank[order]], a_loc[pa[order]], b_loc[pb[order]]
    bounds = np.searchsorted(group, np.arange(p * p + 1))
    entries = np.diff(bounds).reshape(p, p)
    length = max(1, min(SPGEMM_CHUNK, int(entries.max(initial=0))))
    chunked = {}
    for g in range(p * p):
        lo, hi = bounds[g], bounds[g + 1]
        if hi == lo:
            continue
        r = rank[lo:hi]
        first = np.ones(len(r), bool)
        np.not_equal(r[1:], r[:-1], out=first[1:])
        last = np.ones(len(r), bool)
        last[:-1] = first[1:]
        fl = first * 1 + 2 + last * 4
        chunked[divmod(g, p)] = chunk_schedule((r, pa[lo:hi], pb[lo:hi], fl),
                                               length)
    n_chunks = max([1] + [len(c) for c in chunked.values()])
    out = np.zeros((p, p, n_chunks, 4, length), np.int32)
    for (d, s), c in chunked.items():
        out[d, s, :len(c)] = c
    return out, entries


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


def replay_chunks(out, a_blocks, b_blocks, chunks, *, bs, interpret,
                  accumulate=False):
    """Replay a chunked worklist (``chunk_schedule``) into ``out`` on the
    Pallas kernel, one call per chunk, each call aliasing ``out`` so the
    blocks it does not visit survive (with ``accumulate``, the blocks it
    visits gain the products instead of being replaced by them).  Chunks
    whose flags are all off (the ring's stage padding) are never
    launched: a Pallas output block is written back on every visit, even
    one whose body never stored.

    Operands and ``out`` may hold a plane axis, ``(nnzb, g, *block)``
    (``block_spgemm_kernel``): the worklist is walked once and every grid
    step multiplies all ``g`` planes, so ``g`` products cost the steps of
    one."""
    n_real = jnp.sum(jnp.any(chunks[:, 3, :] != 0, axis=1))

    def body(c, out):
        rank, pa, pb, flags = (chunks[c, i] for i in range(4))
        return block_spgemm_kernel(a_blocks, b_blocks, rank, pa, pb, flags,
                                   out, bs=bs, interpret=interpret,
                                   accumulate=accumulate)

    return jax.lax.fori_loop(0, n_real, body, out)


@functools.partial(jax.jit,
                   static_argnames=("nnzb_out", "bs", "interpret"))
def _block_spgemm_pallas(a_blocks, b_blocks, chunks, *, nnzb_out, bs,
                         interpret):
    out = jnp.zeros((nnzb_out,) + a_blocks.shape[1:], jnp.float32)
    return replay_chunks(out, a_blocks, b_blocks, chunks, bs=bs,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bs",))
def _xla_chunk_add(out, a_blocks, b_blocks, rank, pa, pb, flags, *, bs):
    """One worklist chunk: gather, batched matmul, segment-add into ``out``.
    Blocks are stored as the Pallas kernel stores them (``(bs, bs)`` or
    lane-dense, with or without a plane axis) and multiplied plane by
    plane.  Zero-fill entries (real-bit off) gather block 0 but
    contribute nothing."""
    real = ((flags >> 1) & 1).astype(jnp.float32)
    prods = jnp.einsum("w...ij,w...jk->w...ik",
                       unpack_blocks(a_blocks[pa], bs).astype(jnp.float32),
                       unpack_blocks(b_blocks[pb], bs).astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    prods = prods * real.reshape((-1,) + (1,) * (prods.ndim - 1))
    return out.at[rank].add(pack_blocks(prods, out.shape[-2]))


#: peak f32 elements the XLA executor materializes per worklist chunk
#: (~64 MB); bounds device memory at O(chunk * bs^2) instead of O(W * bs^2)
#: for huge worklists, where one unchunked einsum could out-allocate the
#: very densify this pipeline removed
_XLA_CHUNK_ELEMS = 1 << 24


def block_spgemm_xla(out, a_blocks, b_blocks, rank, pa, pb, flags, *, bs):
    """XLA replay of a worklist, adding its products into ``out``, in
    chunks to bound peak memory.

    Chunks are independent partial sums into the same output (the rank
    segment-add is associative), so first/last flags are irrelevant here —
    only the real-bit is consulted, and padding entries (real-bit off)
    may sit anywhere.  The tail chunk is padded with real-bit-off entries
    to keep exactly one compiled chunk shape.
    """
    W = int(rank.shape[0])
    chunk = max(1, _XLA_CHUNK_ELEMS // math.prod(a_blocks.shape[1:]))
    if W <= chunk:
        return _xla_chunk_add(out, a_blocks, b_blocks, rank, pa, pb, flags,
                              bs=bs)
    pad = -W % chunk
    if pad:
        z = jnp.zeros(pad, rank.dtype)
        rank, pa, pb = (jnp.concatenate([x, z]) for x in (rank, pa, pb))
        flags = jnp.concatenate([flags, z])
    for s in range(0, W + pad, chunk):
        e = s + chunk
        out = _xla_chunk_add(out, a_blocks, b_blocks, rank[s:e], pa[s:e],
                             pb[s:e], flags[s:e], bs=bs)
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
        blocks_a = jnp.zeros((1,) + blocks_a.shape[1:], blocks_a.dtype)
    if blocks_b.shape[0] == 0:
        blocks_b = jnp.zeros((1,) + blocks_b.shape[1:], blocks_b.dtype)
    if backend == "pallas":
        with obs.span("spgemm.chunk") as sp:
            chunks = chunk_schedule(schedule,
                                    min(SPGEMM_CHUNK, len(schedule[0])))
            # grid steps the replay launches, each over every plane
            sp.set(chunks=len(chunks),
                   planes=blocks_a.shape[1] if blocks_a.ndim == 4 else 1,
                   steps=chunks.shape[0] * chunks.shape[2])
        return _block_spgemm_pallas(blocks_a, blocks_b, to_device(chunks),
                                    nnzb_out=M.nnzb, bs=bs,
                                    interpret=interpret)
    rank, pa, pb, flags = (to_device(x) for x in schedule)
    out = jnp.zeros((M.nnzb,) + blocks_a.shape[1:], jnp.float32)
    return block_spgemm_xla(out, blocks_a, blocks_b, rank, pa, pb, flags,
                            bs=bs)


def block_spgemm(A: BCSR, B: BCSR, M: BCSR, *, interpret=None,
                 backend: Optional[str] = None,
                 schedule: Optional[Schedule] = None) -> BCSR:
    """C = M (.) (A B) at tile granularity.  Output structure == M structure
    (the 1P allocation); zero blocks are kept (callers may prune via
    ``bcsr_to_csr``).

    An all-empty mask is a defined degenerate case: the worklist is empty
    and an empty BCSR is returned without launching a kernel.  Pass a
    precomputed ``schedule`` to amortize the symbolic phase across several
    numeric replays of one structure.
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
                                interpret=None,
                                backend: Optional[str] = None) -> BCSR:
    """Values and structural counts of C = M (.) (A B) from ONE walk of
    one schedule.

    ``A.blocks`` and ``B.blocks`` are stacked ``(nnzb, 2, rows, lanes)``,
    blocks stored as ``kernel.block_layout(bs)`` says: plane 0 the values,
    plane 1 the 0/1 pattern of the operand's *stored entries*
    (``bcsr_from_csr(..., pattern=True)``; the row kernels treat an
    explicitly stored 0.0 as structural).  The result's blocks are stacked
    and stored the same way: plane 0 the values, plane 1 the count of
    structural contributions, so ``count > 0`` is exact element-level
    presence, identical to the row kernels' structural semantics even
    when numeric cancellation leaves a stored 0.0 in plane 0.
    """
    assert A.block_size == B.block_size == M.block_size
    bs = A.block_size
    shape = (M.shape[0], B.shape[1])
    if M.nnzb == 0:
        return BCSR(M.indptr.copy(), M.indices.copy(),
                    jnp.zeros((0,) + A.blocks.shape[1:], jnp.float32),
                    shape, bs)
    schedule = build_spgemm_schedule(A, B, M)
    blocks = _run_schedule(A, B, M, schedule, A.blocks, B.blocks,
                           interpret=interpret, backend=backend)
    return BCSR(M.indptr.copy(), M.indices.copy(), blocks, shape, bs)


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
