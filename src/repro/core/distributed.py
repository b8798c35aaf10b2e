"""Distributed Masked SpGEMM under ``shard_map`` (beyond-paper scale-out).

The paper is a shared-memory study; its row-parallel decomposition extends
naturally across a mesh:

* ``row_parallel_masked_spgemm`` — 1D: rows of A and M are sharded over the
  mesh's data axes; B is replicated.  Zero communication in the numeric
  phase (the paper's OpenMP loop, across pods).  This is the right regime
  for nnz(B) small vs aggregate memory — typical graph masks.

* ``ring_sparse_masked_spgemm`` — 1.5D sparse ring-SUMMA on BCSR operands
  when B is too large to replicate: block rows are dealt to the devices
  round robin, A/M's as row panels and B's as *occupied* BCSR K-slabs
  that rotate around the ring via ``jax.lax.ppermute`` (value and pattern
  stacked as two planes of one block array, padded to the ring-wide max
  so every rotation has one static shape).  Each stage replays a
  host-built worklist on the block executors, both planes in one walk
  (Pallas on TPU, chunked XLA elsewhere) — no dense ``(k, n)``
  or ``(m, n)`` array exists anywhere on this path, which is what makes
  it usable at scales where ``ring_masked_matmul``'s dense operands
  would not fit.

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
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import caches
from repro import obs

from repro.kernels.masked_matmul.kernel import block_layout, block_position

from .formats import BCSR, CSR, PaddedCSR, padded_from_csr, to_device
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
    """One ring stage on the chunked-XLA executor (``ops.block_spgemm_xla``):
    the stage's chunked worklist read flat (padding entries have the
    real-bit off), its products added plane by plane into the running
    panel accumulator."""
    from repro.kernels.masked_matmul.ops import block_spgemm_xla
    rank, pa, pb, flags = (chunks[:, i, :].reshape(-1) for i in range(4))
    return block_spgemm_xla(out, a_blocks, b_blocks, rank, pa, pb, flags,
                            bs=bs)


def _ring_stage_pallas(out, a_blocks, b_blocks, chunks, *, bs, interpret):
    """One ring stage on the Pallas executor: the replay adds each visited
    rank's products, every plane in one walk, into the running
    accumulator in place (``accumulate``); ranks the stage does not visit
    keep their value."""
    from repro.kernels.masked_matmul.ops import replay_chunks
    return replay_chunks(out, a_blocks, b_blocks, chunks, bs=bs,
                         interpret=interpret, accumulate=True)


@functools.lru_cache(maxsize=64)
def _ring_sparse_program(mesh: Mesh, axis: str, p: int, bs: int, wa: int,
                         wb: int, wm: int, pm: int, rows_loc: int,
                         backend: str, interpret: Optional[bool]):
    """Compiled sparse-ring program (cached: see _row_parallel_program).
    Worklist and entry-list lengths vary per problem and are handled by
    the jit cache; only the quantities baked into the trace are keys here.

    Each device builds its blocks from the entries it is sent (values and
    their block coordinates, ``_ring_prep``): its row panel of A (``wa``
    blocks) and its B K-slab (``wb``), each stacked ``(w, 2, rows,
    lanes)``: plane 0 the values, plane 1 the stored-entry pattern.  The
    slab rotates around the ring, one ``ppermute`` a stage, while each
    stage's worklist is walked once and its products add in place into
    the panel's stacked accumulator (``wm`` blocks: plane 0 values, plane
    1 structural counts).  Blocks are stored lane-dense
    (``block_layout``), so a device holds eight planes of ``wa``, ``wb``
    or ``wm`` blocks of ``bs * bs`` words: A's two, B's two and the two
    in flight, and the accumulator's two.

    The mask-aligned extraction runs inside the shard program: every mask
    element lives in exactly one row panel, so each device scatters its
    own elements into its ``(rows_loc, pm)`` output shard — no
    cross-device gather of block panels ever happens.
    """
    rows, lanes = block_layout(bs)
    if backend == "xla":
        apply_stage = functools.partial(_ring_stage_xla, bs=bs)
    else:
        apply_stage = functools.partial(_ring_stage_pallas, bs=bs,
                                        interpret=interpret)

    def blocks(n, at, values):
        """Values in plane 0, 1.0 at every stored entry in plane 1."""
        return jnp.zeros((n, 2, rows, lanes), jnp.float32).at[
            at[0], :, at[1], at[2]].set(
                jnp.stack([values, jnp.ones_like(values)], axis=1),
                mode="drop")

    def local(a_at, a_vals, b_at, b_vals, sched, ex):
        a_at, a_vals, b_at, b_vals, sched, ex = (
            x[0] for x in (a_at, a_vals, b_at, b_vals, sched, ex))
        a, b = blocks(wa, a_at, a_vals), blocks(wb, b_at, b_vals)
        ring = [(i, (i + 1) % p) for i in range(p)]

        def compute(s, acc, b):
            chunks = jax.lax.dynamic_index_in_dim(sched, s, 0, keepdims=False)
            return apply_stage(acc, a, b, chunks)

        def stage(s, carry):
            acc, b = carry
            # send the slab on first -> XLA overlaps the collective with
            # this stage's block products
            nxt = jax.lax.ppermute(b, axis, ring)
            return compute(s, acc, b), nxt

        acc = jnp.zeros((wm, 2, rows, lanes), jnp.float32)
        # the last stage is peeled: its prefetched slab would be dropped,
        # so only p-1 slab rotations are ever transmitted
        acc, b = jax.lax.fori_loop(0, p - 1, stage, (acc, b))
        acc = compute(p - 1, acc, b)
        # panel-local extraction (padding entries carry rowl == rows_loc,
        # dropped by the out-of-bounds scatter mode)
        loc, r, c, rowl, slot = (ex[i] for i in range(5))
        out_v = jnp.zeros((rows_loc, pm), jnp.float32).at[rowl, slot].set(
            acc[loc, 0, r, c], mode="drop")
        out_p = jnp.zeros((rows_loc, pm), bool).at[rowl, slot].set(
            acc[loc, 1, r, c] > 0, mode="drop")
        # row-sharded over the axis: (p * rows_loc, pm), panel by panel
        return out_v, out_p

    spec = P(axis)
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec,) * 6,
        out_specs=(spec, spec), check_vma=False))


@jax.jit
def _unpanel(vals, present, row_at):
    """The ring's panel-by-panel rows back in the mask's row order."""
    return vals[row_at], present[row_at]


def ring_owner(block_rows: int, p: int) -> np.ndarray:
    """The device that holds each block row on the sparse ring: dealt
    round robin.  Degree-ordered graphs put their hubs first and their
    isolated vertices last, so contiguous panels of equal rows give the
    first device most blocks and products and the last none; neighbouring
    block rows carry similar loads, so dealing them out evens the blocks,
    the products per device and the products of every (panel, slab)
    stage together."""
    return np.arange(block_rows, dtype=np.int64) % p


def _block_structure(x: CSR, bs: int) -> Tuple[BCSR, np.ndarray, np.ndarray]:
    """``x``'s block structure (a BCSR without blocks), each entry's row
    and each entry's block (CSR order)."""
    m, n = x.shape
    nb = -(-n // bs)
    rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(x.indptr))
    uniq, block = np.unique((rows // bs) * nb + x.indices // bs,
                            return_inverse=True)
    mb = -(-m // bs)
    indptr = np.zeros(mb + 1, np.int64)
    np.cumsum(np.bincount(uniq // nb, minlength=mb), out=indptr[1:])
    struct = BCSR(indptr, uniq % nb, np.zeros((0, bs, bs), np.float32),
                  (m, n), bs)
    return struct, rows, block.reshape(-1)


def _by_device(device: np.ndarray, p: int, columns, fills) -> np.ndarray:
    """int64 ``(p, len(columns), width)``: each column's items grouped by
    ``device`` in their order, each device's row padded with its fill."""
    from repro.kernels.masked_matmul.ops import local_positions
    local, counts = local_positions(device, p)
    out = np.empty((p, len(columns), max(1, int(counts.max(initial=0)))),
                   np.int64)
    for i, (col, fill) in enumerate(zip(columns, fills)):
        out[:, i, :] = fill
        out[device, i, local] = col
    return out


def _entry_scatter(x: CSR, bs: int, owner: np.ndarray, p: int):
    """Where each stored entry of ``x`` goes on the ring.

    Returns ``(struct, at, perm, counts)``: device ``d`` scatters its
    entries' values ``x.data[perm[d]]`` into its blocks at
    ``at[d] = (block, row, lane)``; padding points past the last block
    (dropped by the scatter) and at entry ``x.nnz``, a zero appended to the
    values.  ``counts[d]`` blocks of ``x`` lie on device ``d``."""
    from repro.kernels.masked_matmul.ops import (block_devices,
                                                 local_positions)
    struct, rows, block = _block_structure(x, bs)
    device = block_devices(struct.indptr, owner)
    local, counts = local_positions(device, p)
    r, c = block_position(rows % bs, x.indices % bs, bs)
    width = max(1, int(counts.max(initial=0)))
    grouped = _by_device(device[block], p,
                         (local[block], r, c, np.arange(x.nnz)),
                         (width, 0, 0, x.nnz))
    return struct, grouped[:, :3].astype(np.int32), grouped[:, 3], counts


#: host-prep cache for the sparse ring, keyed on operand *structure*
#: (CRC signatures) + block size + ring size: schedules, scatter
#: coordinates, and extraction addressing are all structure-pure, so
#: repeated structures (the serving case; every plan-cache hit) skip
#: straight to the value gather + device program.  Capacity:
#: $REPRO_RING_PREP_CAP or ``repro.caches.set_capacity("ring-prep", n)``.
_ring_prep_cache = caches.LRUCache("ring-prep", 32,
                                   env_var="REPRO_RING_PREP_CAP")


def _ring_prep(A: CSR, B: CSR, M: CSR, bs: int, p: int,
               wm: Optional[int]) -> dict:
    from repro.core.planner import structure_signature
    from repro.kernels.masked_matmul.ops import (block_devices,
                                                 build_ring_schedules,
                                                 local_positions)

    key = (structure_signature(A), structure_signature(B),
           structure_signature(M), bs, p, wm)
    # host prep is pure structure arithmetic (partition, scatter maps,
    # ring schedules) — it embeds no cost-model decision, so a
    # calibration change cannot stale it; deliberately token-free
    hit = _ring_prep_cache.get(key)  # lint: plan-key-ok(structure-pure prep)
    if hit is not None:
        return hit

    with obs.span("spgemm.ring_prep", p=p, bs=bs) as sp:
        m = A.shape[0]
        mb = -(-m // bs)
        owner = ring_owner(mb, p)
        k_owner = ring_owner(-(-B.shape[0] // bs), p)
        Ab, a_at, a_perm, a_counts = _entry_scatter(A, bs, owner, p)
        Bb, b_at, b_perm, b_counts = _entry_scatter(B, bs, k_owner, p)
        Mb, rows, block = _block_structure(M, bs)
        sched, entries = build_ring_schedules(Ab, Bb, Mb, owner, k_owner, p)

        # extraction: each device scatters its own mask elements into its
        # (rows_loc, pm) output shard, block row by block row in its order
        device = block_devices(Mb.indptr, owner)
        m_loc, m_counts = local_positions(device, p)
        row_loc, row_counts = local_positions(owner, p)
        rows_loc = int(row_counts.max()) * bs
        local_row = row_loc[rows // bs] * bs + rows % bs
        r, c = block_position(rows % bs, M.indices % bs, bs)
        slots = np.arange(M.nnz, dtype=np.int64) - M.indptr[rows]
        ex = _by_device(device[block], p,
                        (m_loc[block], r, c, local_row, slots),
                        (0, 0, 0, rows_loc, 0)).astype(np.int32)
        g = np.arange(m, dtype=np.int64)
        row_at = owner[g // bs] * rows_loc + row_loc[g // bs] * bs + g % bs
        M_p = padded_from_csr(M, wm)
        prep = dict(
            a_at=a_at, a_perm=a_perm, b_at=b_at, b_perm=b_perm, sched=sched,
            ex=ex, row_at=row_at.astype(np.int32), rows_loc=rows_loc,
            wa=max(1, int(a_counts.max())), wb=max(1, int(b_counts.max())),
            wm=max(1, int(m_counts.max())), mask_cols=M_p.cols,
            pm=M_p.width, on_mesh={})
        # grid steps the busiest chip launches a solve, each over both
        # planes (values, pattern) of the program's stacked blocks
        launched = (sched[:, :, :, 3, :] != 0).any(axis=-1).sum(axis=(1, 2))
        sp.set(blocks=a_counts.tolist(), slab_blocks=b_counts.tolist(),
               entries=entries.sum(axis=1).tolist(),
               stage_entries=entries.max(axis=0).tolist(), planes=2,
               steps=int(launched.max()) * sched.shape[-1])
    _ring_prep_cache.put(key, prep)  # lint: plan-key-ok(structure-pure prep)
    return prep


def clear_ring_prep_cache() -> None:
    _ring_prep_cache.clear()


def ring_sparse_masked_spgemm(A: CSR, B: CSR, M: CSR, mesh: Mesh, *,
                              axis: str = "data",
                              block_size: Optional[int] = None,
                              backend: Optional[str] = None,
                              interpret: Optional[bool] = None,
                              wm: Optional[int] = None) -> MaskedSpGEMMResult:
    """C = M (.) (A B) on a sparse BCSR ring: A/M row panels sharded over
    ``axis``, B's occupied K-slabs rotating via ``ppermute``.

    Block rows are dealt to the devices round robin (``ring_owner``), for
    A and M and for B's K-slabs alike, which balances blocks and products
    on degree-ordered graphs.  Densify-free end to end: each device is
    sent its entries' values and builds its occupied blocks of A and of
    its B slab (values + stored-entry pattern, padded to the ring max so
    ``ppermute`` sees one static shape); each stage replays a host-built
    worklist on the block executor, adding into the panel's accumulators
    in place.  ``present`` comes from a structural counting replay sharing
    the same schedules, so results are bitwise the single-device
    ``masked_spgemm`` semantics, including cancellation and explicitly
    stored zeros.

    Host prep (partition, schedules, scatter coordinates, extraction
    addressing, in the ``spgemm.ring_prep`` span) is pure structure and
    cached by structural signature, its device copies per mesh: repeated
    structures, the serving case, pay only the value gather
    (``spgemm.host_prep``) and the compiled device program.

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
    sharded = NamedSharding(mesh, P(axis))
    with obs.span("spgemm.host_prep", algorithm="ring"):
        a_vals = np.append(A.data, 0).astype(np.float32)[prep["a_perm"]]
        b_vals = np.append(B.data, 0).astype(np.float32)[prep["b_perm"]]
        a_vals = to_device(a_vals, sharding=sharded)
        b_vals = to_device(b_vals, sharding=sharded)
    held = prep["on_mesh"].get((mesh, axis))
    if held is None:
        held = prep["on_mesh"][mesh, axis] = (
            tuple(to_device(prep[k], sharding=sharded)
                  for k in ("a_at", "b_at", "sched", "ex")),
            to_device(prep["row_at"], sharding=NamedSharding(mesh, P())))
    (a_at, b_at, sched, ex), row_at = held

    run = _ring_sparse_program(mesh, axis, p, bs, prep["wa"], prep["wb"],
                               prep["wm"], prep["pm"], prep["rows_loc"],
                               backend, it)
    vals, present = _unpanel(*run(a_at, a_vals, b_at, b_vals, sched, ex),
                             row_at)
    return MaskedSpGEMMResult(vals, present, prep["mask_cols"], (m, n))


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


@functools.lru_cache(maxsize=8)
def device_mesh(devices: int, axis: str = "data") -> Mesh:
    """A 1-D mesh over the first ``devices`` devices, one object per size
    so the compiled programs' caches, keyed on the mesh, stay warm."""
    avail = jax.devices()
    if not 1 <= devices <= len(avail):
        raise ValueError(f"{devices} devices asked for, {len(avail)} found")
    return Mesh(np.array(avail[:devices]), (axis,))


# ---------------------------------------------------------------------------
# helpers for building sharded problems
# ---------------------------------------------------------------------------


# the compiled shard_map programs are lru_cache-bounded; registering them
# lets ``repro.caches.clear_all()`` drop compiled state in one sweep
caches.register_lru("dist-row-program", _row_parallel_program)
caches.register_lru("dist-dense-ring-program", _ring_dense_program)
caches.register_lru("dist-sparse-ring-program", _ring_sparse_program)
caches.register_lru("dist-device-mesh", device_mesh)


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
