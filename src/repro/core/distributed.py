"""Distributed Masked SpGEMM under ``shard_map`` (beyond-paper scale-out).

The paper is a shared-memory study; its row-parallel decomposition extends
naturally across a mesh:

* ``row_parallel_masked_spgemm`` — 1D: rows of A and M are sharded over the
  mesh's data axes; B is replicated.  Zero communication in the numeric
  phase (the paper's OpenMP loop, across pods).  This is the right regime
  for nnz(B) small vs aggregate memory — typical graph masks.

* ``ring_sparse_masked_spgemm`` — 1.5D sparse ring-SUMMA on BCSR operands
  when B is too large to replicate: A/M row-block-panels are sharded, B's
  *occupied* BCSR K-slabs rotate around the ring via ``jax.lax.ppermute``
  (each panel = ``(nnzb_slab, bs, bs)`` value+pattern blocks, padded to the
  ring-wide max so every rotation has one static shape).  Each stage
  replays a host-built K-slab worklist on the block executors (Pallas on
  TPU, chunked XLA elsewhere) — no dense ``(k, n)`` or ``(m, n)`` array
  exists anywhere on this path, which is what makes it usable at scales
  where ``ring_masked_matmul``'s dense operands would not fit.

* ``ring_masked_matmul`` — the dense 1.5D ring (tile-granular skipping),
  kept for dense-operand workloads and as the bench baseline the sparse
  ring is measured against.

``distributed_masked_spgemm`` is the driver-level entry point: it takes
host CSR operands plus a mesh and elects row-parallel vs the sparse ring
via the planner's distributed cost model (replication bytes vs ring volume
vs per-stage tile cost), mirroring ``masked_spgemm(algorithm="auto")`` on
one device.  The model's ``DIST_COST`` constants are per-backend
calibration data: ``python -m repro.tune --only dist`` refits them from
measured ring/row probes (forced host devices stand in for a real
network, so refit on the actual mesh before trusting auto at scale).

All device programs are pure ``shard_map``: they lower and compile for any
mesh (including the 512-chip production mesh) and are exercised by the
dry-run and the forced-multi-device CPU harness in ``tests/``.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import caches
from repro import obs

from .formats import CSR, PaddedCSR, bcsr_row_panels, padded_from_csr
from .masked_spgemm import MaskedSpGEMMResult, _row_fn
from .semiring import Semiring, PLUS_TIMES


# ---------------------------------------------------------------------------
# 1D row-parallel: the paper's decomposition across the mesh
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _row_parallel_program(mesh: Mesh, axes: Tuple[str, ...], algorithm: str,
                          n: int, kdim: int, semiring: Semiring,
                          complement: bool, n_inspect: Optional[int]):
    """Compiled row-parallel program, cached so repeated calls (the
    serving loop, timed bench iterations) never re-trace or re-compile —
    the jit cache keys the remaining variation (operand shapes/widths)."""
    row = _row_fn(algorithm, n, kdim, semiring, complement, n_inspect)
    spec = P(axes)

    def local(mc, ac, av, al, Bc, Bv, Bl):
        f = jax.vmap(lambda mcr, acr, avr, alr:
                     row(mcr, acr, avr, alr, Bc, Bv, Bl))
        return f(mc, ac, av, al)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec, spec, P(), P(), P()),
        out_specs=(spec, spec), check_vma=False,
    ))


def row_parallel_masked_spgemm(A: PaddedCSR, B: PaddedCSR, M: PaddedCSR,
                               mesh: Mesh, *, algorithm: str = "msa",
                               semiring: Semiring = PLUS_TIMES,
                               complement: bool = False,
                               n_inspect: Optional[int] = None,
                               axes: Sequence[str] = ("data",)):
    """C = M (.) (A B), rows of A/M sharded over ``axes``, B replicated.

    Returns (vals, present) mask-aligned, sharded like the mask rows.
    For ``algorithm="inner"`` pass B already transposed (PaddedCSR of B^T,
    the same contract as the single-device driver); output shape comes
    from the mask, so a transposed B never skews it.
    """
    n = M.shape[1]
    kdim = A.shape[1]
    shard = _row_parallel_program(mesh, tuple(axes), algorithm, n, kdim,
                                  semiring, complement, n_inspect)
    return shard(M.cols, A.cols, A.vals, A.lens, B.cols, B.vals, B.lens)


# ---------------------------------------------------------------------------
# 1.5D ring-SUMMA masked matmul (tile-granular, dense panels)
# ---------------------------------------------------------------------------


def ring_masked_matmul(a, b, mask, mesh: Mesh, *, axis: str = "data",
                       block: int = 128, precision=None):
    """C = mask (.) (A B) with A row-sharded and B K-sharded over ``axis``.

    a: (m, k) sharded P(axis, None); b: (k, n) sharded P(axis, None);
    mask: (m, n) {0,1} sharded P(axis, None).

    Tile-granular skipping, per stage: each shard computes its mask's
    block-level occupancy once (any nonzero per ``block x block`` tile);
    inside every ring stage the local product is issued per output column
    panel, and panels whose tiles are all disallowed skip their MXU work
    through ``lax.cond`` (the dot is never executed, every stage).  After
    the loop, disallowed output tiles are zeroed at block granularity and
    the element mask applied once.  The ppermute for stage s+1 is issued
    *before* stage s's local compute so XLA's async collectives overlap
    communication with the MXU work; the last stage is peeled so the HLO
    contains exactly nsteps-1 collective-permutes of one B panel each
    (the nsteps-th rotation would only restore the starting layout).

    Returns (m, n) sharded P(axis, None).
    """
    shard = _ring_dense_program(mesh, axis, block, precision)
    return shard(a, b, mask)


@functools.lru_cache(maxsize=64)
def _ring_dense_program(mesh: Mesh, axis: str, block: int, precision):
    """Compiled dense-ring program (cached: see _row_parallel_program)."""
    nsteps = mesh.shape[axis]

    def local(a_blk, b_blk, m_blk):
        # a_blk: (m/p, k); b_blk: (k/p, n); m_blk: (m/p, n)
        idx = jax.lax.axis_index(axis)
        k_per, n = b_blk.shape
        m_loc = a_blk.shape[0]
        tm, tn = min(block, m_loc), min(block, n)
        pad_m, pad_n = -m_loc % tm, -n % tn
        mp, np_ = m_loc + pad_m, n + pad_n
        tiles_m, tiles_n = mp // tm, np_ // tn

        # block-level occupancy of this shard's mask rows (computed once);
        # padded columns/rows are zero -> their tiles are never scheduled
        m_pad = jnp.pad(m_blk != 0, ((0, pad_m), (0, pad_n)))
        occ = m_pad.reshape(tiles_m, tm, tiles_n, tn).any(axis=(1, 3))
        col_needed = occ.any(axis=0)            # (tiles_n,)
        a_pad = jnp.pad(a_blk, ((0, pad_m), (0, 0)))
        b_pad = jnp.pad(b_blk, ((0, 0), (0, pad_n)))

        def compute(s, acc, panel):
            src = (idx - s) % nsteps          # whose panel we now hold
            a_slice = jax.lax.dynamic_slice_in_dim(a_pad, src * k_per, k_per,
                                                   axis=1)

            def col_panel(tj, acc):
                panel_j = jax.lax.dynamic_slice_in_dim(panel, tj * tn, tn,
                                                       axis=1)
                contrib = jax.lax.cond(
                    col_needed[tj],
                    lambda: jnp.dot(a_slice, panel_j,
                                    preferred_element_type=jnp.float32,
                                    precision=precision),
                    lambda: jnp.zeros((mp, tn), jnp.float32))
                cur = jax.lax.dynamic_slice_in_dim(acc, tj * tn, tn, axis=1)
                return jax.lax.dynamic_update_slice_in_dim(
                    acc, cur + contrib, tj * tn, axis=1)

            return jax.lax.fori_loop(0, tiles_n, col_panel, acc)

        def stage(s, carry):
            acc, panel = carry
            # prefetch next panel first -> XLA overlaps with the matmul
            nxt = jax.lax.ppermute(
                panel, axis,
                [(i, (i + 1) % nsteps) for i in range(nsteps)])
            acc = compute(s, acc, panel)
            return acc, nxt

        acc = jnp.zeros((mp, np_), jnp.float32)
        # last stage peeled: its prefetched panel would be dropped, so only
        # nsteps-1 rotations are transmitted
        acc, panel = jax.lax.fori_loop(0, nsteps - 1, stage, (acc, b_pad))
        acc = compute(nsteps - 1, acc, panel)
        # zero disallowed tiles at block granularity, then the element mask
        occ_elem = jnp.repeat(jnp.repeat(occ, tm, axis=0), tn, axis=1)
        acc = jnp.where(occ_elem, acc, 0.0)[:m_loc, :n]
        return jnp.where(m_blk != 0, acc, 0.0).astype(a_blk.dtype)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None)),
        out_specs=P(axis, None), check_vma=False,
    ))


# ---------------------------------------------------------------------------
# 1.5D sparse ring-SUMMA on BCSR panels (densify-free distributed tile route)
# ---------------------------------------------------------------------------


def _ring_stage_xla(out, a_blocks, b_blocks, chunks, *, bs):
    """One ring stage on the chunked-XLA executor: gather, batched matmul,
    segment-add into the running panel accumulator.  The stage's chunked
    worklist is read flat (padding entries have the real-bit off), in
    pieces like ``ops._block_spgemm_xla`` so peak memory stays
    O(piece * bs^2)."""
    from repro.kernels.masked_matmul.ops import _XLA_CHUNK_ELEMS
    rank, pa, pb, flags = (chunks[:, i, :].reshape(-1) for i in range(4))
    ws = int(rank.shape[0])
    piece = max(1, _XLA_CHUNK_ELEMS // (bs * bs))
    for s0 in range(0, ws, piece):
        e = min(ws, s0 + piece)
        real = ((flags[s0:e] >> 1) & 1).astype(jnp.float32)
        prods = jnp.einsum("wij,wjk->wik",
                           a_blocks[pa[s0:e]].astype(jnp.float32),
                           b_blocks[pb[s0:e]].astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        out = out.at[rank[s0:e]].add(prods * real[:, None, None])
    return out


def _ring_stage_pallas(out, a_blocks, b_blocks, chunks, *, bs, interpret):
    """One ring stage on the Pallas executor: the worklist covers every
    output rank (zero-fill + padding-rank entries from
    ``build_ring_schedules``), so the replay's output is fully defined and
    adds into the running accumulator."""
    from repro.kernels.masked_matmul.ops import replay_chunks
    stage = replay_chunks(jnp.zeros_like(out), a_blocks, b_blocks, chunks,
                          bs=bs, interpret=interpret)
    return out + stage


@functools.lru_cache(maxsize=64)
def _ring_sparse_program(mesh: Mesh, axis: str, p: int, bs: int,
                         wm_blocks: int, pm: int, rows_loc: int,
                         backend: str, interpret: Optional[bool]):
    """Compiled sparse-ring program (cached: see _row_parallel_program).
    Panel/worklist lengths vary per problem and are handled by the jit
    cache; only the quantities baked into the trace are keys here.

    The mask-aligned extraction runs inside the shard program: every mask
    element lives in exactly one row-panel, so each device scatters its own
    elements into its ``(rows_loc, pm)`` output shard — no cross-device
    gather of block panels ever happens.
    """
    if backend == "xla":
        apply_stage = functools.partial(_ring_stage_xla, bs=bs)
    else:
        apply_stage = functools.partial(_ring_stage_pallas, bs=bs,
                                        interpret=interpret)

    def local(av, ap, bv, bp, sc, loc, roff, coff, rowl, slot):
        av, ap, bv, bp, sc = av[0], ap[0], bv[0], bp[0], sc[0]
        loc, roff, coff, rowl, slot = (x[0] for x in
                                       (loc, roff, coff, rowl, slot))
        panel = jnp.stack([bv, bp])        # values+pattern rotate together

        def compute(s, vals, cnts, pan):
            chunks = jax.lax.dynamic_index_in_dim(sc, s, 0, keepdims=False)
            vals = apply_stage(vals, av, pan[0], chunks)
            cnts = apply_stage(cnts, ap, pan[1], chunks)
            return vals, cnts

        def stage(s, carry):
            vals, cnts, pan = carry
            # prefetch the next panel first -> XLA overlaps the collective
            # with this stage's block products
            nxt = jax.lax.ppermute(
                pan, axis, [(i, (i + 1) % p) for i in range(p)])
            vals, cnts = compute(s, vals, cnts, pan)
            return vals, cnts, nxt

        vals = jnp.zeros((wm_blocks, bs, bs), jnp.float32)
        cnts = jnp.zeros((wm_blocks, bs, bs), jnp.float32)
        # the last stage is peeled: its prefetched panel would be dropped,
        # so only p-1 panel rotations are ever transmitted
        vals, cnts, panel = jax.lax.fori_loop(0, p - 1, stage,
                                              (vals, cnts, panel))
        vals, cnts = compute(p - 1, vals, cnts, panel)
        # panel-local extraction (padding entries carry rowl == rows_loc,
        # dropped by the out-of-bounds scatter mode)
        out_v = jnp.zeros((rows_loc, pm), jnp.float32)
        out_p = jnp.zeros((rows_loc, pm), bool)
        out_v = out_v.at[rowl, slot].set(vals[loc, roff, coff], mode="drop")
        out_p = out_p.at[rowl, slot].set(cnts[loc, roff, coff] > 0,
                                         mode="drop")
        # row-sharded over the axis: global result is (p * rows_loc, pm)
        return out_v, out_p

    spec = P(axis)
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec,) * 10,
        out_specs=(spec, spec), check_vma=False))


def _panel_scatter(x: CSR, bs: int, p: int) -> Tuple[np.ndarray, ...]:
    """Per-entry scatter coordinates into a (p, W, bs, bs) stacked panel
    array plus the panel block structure.

    Returns ``(indptr_pad, indices, panel, local, r, c, w)``: entry e of
    ``x`` lands in ``stacked[panel[e], local[e], r[e], c[e]]``; ``w`` is
    the max panel nnzb (the ring-wide pad).  Pure structure — values are
    scattered per call.
    """
    m, n = x.shape
    nb = -(-n // bs)
    mb = -(-m // bs)
    mb_pad = -(-mb // p) * p
    rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(x.indptr))
    key = (rows // bs) * nb + x.indices // bs
    uniq, inv = np.unique(key, return_inverse=True)
    ubr, ubc = uniq // nb, uniq % nb
    indptr = np.zeros(mb_pad + 1, dtype=np.int64)
    np.add.at(indptr, ubr + 1, 1)
    indptr = np.cumsum(indptr)
    rows_per = mb_pad // p
    panel_of_block = ubr // rows_per
    local_of_block = np.arange(len(uniq)) - indptr[panel_of_block * rows_per]
    w = max(1, int(np.bincount(panel_of_block, minlength=p).max(initial=0)))
    return (indptr, ubc.astype(np.int64), panel_of_block[inv],
            local_of_block[inv], rows % bs, x.indices % bs, w)


def _struct_panels(indptr: np.ndarray, indices: np.ndarray, p: int, bs: int,
                   ncols: int):
    """Structure-only BCSR row panels (blocks empty; schedule construction
    never reads them)."""
    from .formats import BCSR
    full = BCSR(indptr, indices, np.zeros((0, bs, bs), np.float32),
                ((len(indptr) - 1) * bs, ncols), bs)
    return bcsr_row_panels(full, p)


#: host-prep cache for the sparse ring, keyed on operand *structure*
#: (CRC signatures) + block size + ring size: schedules, scatter
#: coordinates, and extraction addressing are all structure-pure, so
#: repeated structures (the serving case; every plan-cache hit) skip
#: straight to the value scatter + device program.  Capacity:
#: $REPRO_RING_PREP_CAP or ``repro.caches.set_capacity("ring-prep", n)``.
_ring_prep_cache = caches.LRUCache("ring-prep", 32,
                                   env_var="REPRO_RING_PREP_CAP")


def _ring_prep(A: CSR, B: CSR, M: CSR, bs: int, p: int,
               wm: Optional[int]) -> dict:
    from repro.core.planner import structure_signature
    from repro.kernels.masked_matmul.ops import build_ring_schedules

    key = (structure_signature(A), structure_signature(B),
           structure_signature(M), bs, p, wm)
    # host prep is pure structure arithmetic (panelization, scatter maps,
    # ring schedules) — it embeds no cost-model decision, so a
    # calibration change cannot stale it; deliberately token-free
    hit = _ring_prep_cache.get(key)  # lint: plan-key-ok(structure-pure prep)
    if hit is not None:
        return hit

    m, k = A.shape
    n = B.shape[1]
    a_ptr, a_idx, a_pan, a_loc, a_r, a_c, wa = _panel_scatter(A, bs, p)
    b_ptr, b_idx, b_pan, b_loc, b_r, b_c, wb = _panel_scatter(B, bs, p)
    m_ptr, m_idx, m_pan, m_loc, m_r, m_c, wmb = _panel_scatter(M, bs, p)

    A_panels = _struct_panels(a_ptr, a_idx, p, bs, k)
    B_slabs = _struct_panels(b_ptr, b_idx, p, bs, n)
    M_panels = _struct_panels(m_ptr, m_idx, p, bs, n)
    sched = build_ring_schedules(A_panels, B_slabs, M_panels, out_pad=wmb)

    # stored-entry pattern panels are structure-constant: build once
    a_pat = np.zeros((p, wa, bs, bs), np.float32)
    a_pat[a_pan, a_loc, a_r, a_c] = 1.0
    b_pat = np.zeros((p, wb, bs, bs), np.float32)
    b_pat[b_pan, b_loc, b_r, b_c] = 1.0

    # extraction: group mask elements by owning panel; each device
    # scatters its own elements into its (rows_loc, pm) output shard.
    # Padding entries point at row rows_loc -> dropped by scatter mode.
    mr = np.repeat(np.arange(m, dtype=np.int64), np.diff(M.indptr))
    slots = np.arange(M.nnz, dtype=np.int64) - M.indptr[mr]
    M_p = padded_from_csr(M, wm)
    rows_per = (len(m_ptr) - 1) // p
    rows_loc = rows_per * bs
    counts = np.bincount(m_pan, minlength=p)
    max_e = max(1, int(counts.max(initial=0)))
    order = np.argsort(m_pan, kind="stable")
    j = np.arange(M.nnz) - np.concatenate(
        [[0], np.cumsum(counts)[:-1]])[m_pan[order]]
    pan_o = m_pan[order]

    def panelized(values, fill):
        out = np.full((p, max_e), fill, np.int32)
        out[pan_o, j] = values[order]
        return out

    prep = dict(
        a_scatter=(a_pan, a_loc, a_r, a_c, wa), a_pat=a_pat,
        b_scatter=(b_pan, b_loc, b_r, b_c, wb), b_pat=b_pat,
        sched=sched, wm_blocks=wmb, rows_loc=rows_loc,
        ex_loc=panelized(m_loc, 0),
        ex_roff=panelized(mr % bs, 0),
        ex_coff=panelized(m_c, 0),
        ex_rowl=panelized(mr - m_pan * rows_loc, rows_loc),
        ex_slot=panelized(slots, 0),
        mask_cols=M_p.cols, pm=M_p.width)
    _ring_prep_cache.put(key, prep)  # lint: plan-key-ok(structure-pure prep)
    return prep


def clear_ring_prep_cache() -> None:
    _ring_prep_cache.clear()


def ring_prep_cache_info() -> dict:
    return _ring_prep_cache.info()


def ring_sparse_masked_spgemm(A: CSR, B: CSR, M: CSR, mesh: Mesh, *,
                              axis: str = "data",
                              block_size: Optional[int] = None,
                              backend: Optional[str] = None,
                              interpret: Optional[bool] = None,
                              wm: Optional[int] = None) -> MaskedSpGEMMResult:
    """C = M (.) (A B) on a sparse BCSR ring: A/M row-panels sharded over
    ``axis``, B's occupied K-slabs rotating via ``ppermute``.

    Densify-free end to end: CSR operands scatter into occupied blocks,
    every device holds only its row-panel of A/M and one rotating B slab
    (values + stored-entry pattern, padded to the ring max so ``ppermute``
    sees one static shape), and each stage replays a host-built K-slab
    worklist on the block executor.  ``present`` comes from a structural
    counting replay sharing the same schedules, so results are bitwise the
    single-device ``masked_spgemm`` semantics, including cancellation and
    explicitly stored zeros.

    Host prep (schedules, scatter coordinates, extraction addressing) is
    pure structure and cached by structural signature — repeated
    structures, the serving case, pay only the value scatter and the
    compiled device program.

    Only ``plus_times`` with an explicit mask is supported (the executors
    accumulate with a dense dot) — ``distributed_masked_spgemm`` routes
    unsupported products to the row-parallel path.
    """
    from repro.kernels.masked_matmul.ops import resolve_executor

    m, k = A.shape
    k2, n = B.shape
    assert k == k2, (A.shape, B.shape)
    assert M.shape == (m, n), (M.shape, (m, n))
    p = int(mesh.shape[axis])

    if M.nnz == 0:
        M_p = padded_from_csr(M, wm)
        z = jnp.zeros((m, M_p.width), jnp.float32)
        return MaskedSpGEMMResult(z, jnp.zeros((m, M_p.width), bool),
                                  M_p.cols, (m, n))
    if block_size is None:
        from .planner import ring_block_candidates
        block_size = ring_block_candidates(m, k, n)[0]
    bs = block_size
    backend, it = resolve_executor(backend, interpret)

    prep = _ring_prep(A, B, M, bs, p, wm)
    a_pan, a_loc, a_r, a_c, wa = prep["a_scatter"]
    b_pan, b_loc, b_r, b_c, wb = prep["b_scatter"]
    wm_blocks = prep["wm_blocks"]
    a_vals = np.zeros((p, wa, bs, bs), np.float32)
    a_vals[a_pan, a_loc, a_r, a_c] = A.data
    b_vals = np.zeros((p, wb, bs, bs), np.float32)
    b_vals[b_pan, b_loc, b_r, b_c] = B.data

    run = _ring_sparse_program(mesh, axis, p, bs, wm_blocks, prep["pm"],
                               prep["rows_loc"], backend, it)
    vals, present = run(a_vals, prep["a_pat"], b_vals, prep["b_pat"],
                        prep["sched"], prep["ex_loc"], prep["ex_roff"],
                        prep["ex_coff"], prep["ex_rowl"], prep["ex_slot"])
    return MaskedSpGEMMResult(vals[:m], present[:m], prep["mask_cols"],
                              (m, n))


# ---------------------------------------------------------------------------
# Driver-level entry point: route election across the mesh
# ---------------------------------------------------------------------------


def distributed_masked_spgemm(A: CSR, B: CSR, M: CSR, mesh: Mesh, *,
                              algorithm: str = "auto", axis: str = "data",
                              semiring: Semiring = PLUS_TIMES,
                              complement: bool = False,
                              block_size: Optional[int] = None,
                              row_algorithm: Optional[str] = None,
                              backend: Optional[str] = None,
                              interpret: Optional[bool] = None
                              ) -> MaskedSpGEMMResult:
    """C = M (.) (A B) across ``mesh``: the distributed counterpart of
    ``masked_spgemm``.

    ``algorithm``:
      * ``"auto"`` — extend the planner's decision to the mesh: the
        distributed cost model weighs replicating B (row-parallel, zero
        numeric-phase communication) against rotating B's occupied BCSR
        K-slabs around the ring (sparse ring-SUMMA, memory O(nnzb/p) per
        device), plus each route's compute cost.
      * ``"row"``  — force the 1D row-parallel path (B replicated).
      * ``"ring"`` — force the sparse BCSR ring (plus_times, explicit mask).

    Host CSR operands only; returns a mask-aligned ``MaskedSpGEMMResult``
    identical (bitwise, under exact values) to single-device
    ``masked_spgemm`` on the same operands.
    """
    if not isinstance(A, CSR) or not isinstance(B, CSR) \
            or not isinstance(M, CSR):
        raise NotImplementedError(
            "distributed_masked_spgemm needs host CSR operands")
    if complement:
        raise NotImplementedError(
            "complemented masks are not mask-bounded; shard "
            "row_parallel_masked_spgemm directly for that regime")
    if algorithm not in ("auto", "row", "ring"):
        raise ValueError(f"unknown distributed algorithm {algorithm!r}")

    from repro.kernels.masked_matmul.ops import tile_path_supported
    ring_ok = tile_path_supported(semiring.name, complement)
    p = int(mesh.shape[axis])

    if algorithm == "ring" and not ring_ok:
        raise NotImplementedError(
            "sparse ring requires plus_times and an explicit mask")
    if algorithm == "auto":
        from .planner import plan_distributed
        dplan = plan_distributed(A, B, M, p, complement=complement,
                                 semiring=semiring)
        algorithm = dplan.route
        if block_size is None and dplan.tile_block:
            block_size = dplan.tile_block
        if row_algorithm is None:
            row_algorithm = dplan.row_algorithm

    if algorithm == "ring":
        from repro.kernels.masked_matmul.ops import resolve_executor
        backend, interpret = resolve_executor(backend, interpret)
        with obs.span("spgemm.dist", route="ring", p=p,
                      block=block_size or 0, executor=backend,
                      interpret=interpret):
            return ring_sparse_masked_spgemm(
                A, B, M, mesh, axis=axis, block_size=block_size,
                backend=backend, interpret=interpret)

    # row-parallel: replicate B, shard A/M rows, run the row kernels
    m, n = M.shape
    if row_algorithm is None:
        from .planner import collect_stats, rank_algorithms
        stats = collect_stats(A, B, M, complement=complement,
                              semiring=semiring)
        row_algorithm = rank_algorithms(stats, rows=-(-m // p))[0][0]
    with obs.span("spgemm.dist", route="row", p=p,
                  algorithm=row_algorithm):
        with obs.span("spgemm.host_prep", algorithm=row_algorithm):
            if row_algorithm == "inner":
                B_p = padded_from_csr(B.transpose())
            else:
                B_p = padded_from_csr(B)
            A_p = padded_from_csr(A)
            M_p = padded_from_csr(M)
            A_p, M_p = pad_rows_to(p, A_p, M_p)
        vals, present = row_parallel_masked_spgemm(
            A_p, B_p, M_p, mesh, algorithm=row_algorithm,
            semiring=semiring, complement=complement, axes=(axis,))
    return MaskedSpGEMMResult(vals[:m], present[:m], M_p.cols[:m], (m, n))


# ---------------------------------------------------------------------------
# helpers for building sharded problems
# ---------------------------------------------------------------------------


# the compiled shard_map programs are lru_cache-bounded; registering them
# lets ``repro.caches.clear_all()`` drop compiled state in one sweep
caches.register_lru("dist-row-program", _row_parallel_program)
caches.register_lru("dist-dense-ring-program", _ring_dense_program)
caches.register_lru("dist-sparse-ring-program", _ring_sparse_program)


def pad_rows_to(mesh_axis_size: int, *mats: PaddedCSR) -> Tuple[PaddedCSR, ...]:
    """Pad row count to a multiple of the mesh axis so shards are equal."""
    out = []
    for p in mats:
        m, n = p.shape
        target = -(-m // mesh_axis_size) * mesh_axis_size
        if target == m:
            out.append(p)
            continue
        pad = target - m
        cols = jnp.concatenate(
            [p.cols, jnp.full((pad, p.width), n, jnp.int32)])
        vals = jnp.concatenate([p.vals, jnp.zeros((pad, p.width),
                                                  p.vals.dtype)])
        lens = jnp.concatenate([p.lens, jnp.zeros((pad,), jnp.int32)])
        out.append(PaddedCSR(cols, vals, lens, (target, n)))
    return tuple(out)
