"""Sparse matrix storage formats.

The paper (Milakovic et al., "Parallel Algorithms for Masked Sparse
Matrix-Matrix Products", 2021) uses element-level CSR/CSC on CPUs.  JAX/TPU
needs static shapes and tile-granular compute, so we provide three layers:

  * ``CSR`` / ``CSC``          -- host-side (numpy) element formats, used to
                                  build problems and as ground truth.
  * ``PaddedCSR`` (ELL-like)   -- device-friendly element format: every row is
                                  padded to a static width so the paper's
                                  row-parallel algorithms can be ``vmap``-ed.
  * ``BCSR`` / ``BCSC``        -- Block-CSR with MXU-aligned dense tiles; the
                                  TPU-native adaptation of the paper's
                                  algorithms operates on these.

All element formats keep column indices sorted within each row (the paper
assumes sorted inputs for MCA and Heap).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

Array = jax.Array

# --------------------------------------------------------------------------
# Host-side element CSR/CSC (numpy; problem setup + oracles)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CSR:
    """Host-side CSR. indptr:(m+1,) indices:(nnz,) data:(nnz,) shape:(m,n)."""

    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def ncols(self) -> int:
        return self.shape[1]

    def row(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        s, e = self.indptr[i], self.indptr[i + 1]
        return self.indices[s:e], self.data[s:e]

    def row_nnz(self) -> np.ndarray:
        return np.diff(self.indptr)

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=self.data.dtype)
        for i in range(self.shape[0]):
            cols, vals = self.row(i)
            out[i, cols] = vals
        return out

    def transpose(self) -> "CSR":
        """CSR of the transpose (== CSC view of self)."""
        return csr_from_coo(
            self.indices,
            _expand_rows(self.indptr),
            self.data,
            (self.shape[1], self.shape[0]),
        )

    def sorted_rows(self) -> "CSR":
        rows = _expand_rows(self.indptr)
        order = np.lexsort((self.indices, rows))
        return CSR(self.indptr, self.indices[order], self.data[order],
                   self.shape)


def _expand_rows(indptr: np.ndarray) -> np.ndarray:
    """Row index of every nonzero, from indptr."""
    counts = np.diff(indptr)
    return np.repeat(np.arange(len(counts), dtype=np.int64), counts)


def to_device(x, dtype=None, sharding=None) -> Array:
    """``jnp.asarray(x, dtype)``, or with a ``sharding`` ``device_put``
    there: one host-to-device copy, under an ``spgemm.h2d`` span whose
    ``bytes`` are what lands on the devices (a replicated array once per
    device).  The span times the host's part of the call, which may
    return before the transfer has finished."""
    with obs.span("spgemm.h2d") as sp:
        if sharding is None:
            out = jnp.asarray(x, dtype)
            sp.set(bytes=out.nbytes)
        else:
            out = jax.device_put(np.asarray(x, dtype), sharding)
            sp.set(bytes=sum(s.data.nbytes for s in out.addressable_shards))
    return out


def csr_from_coo(rows, cols, vals, shape, sum_dups: bool = True) -> CSR:
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals)
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    if sum_dups and len(rows):
        key = rows * shape[1] + cols
        uniq, inv = np.unique(key, return_inverse=True)
        new_vals = np.zeros(len(uniq), dtype=vals.dtype)
        np.add.at(new_vals, inv, vals)
        rows, cols, vals = uniq // shape[1], uniq % shape[1], new_vals
    indptr = np.zeros(shape[0] + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    indptr = np.cumsum(indptr)
    return CSR(indptr, cols.astype(np.int64), vals, shape)


def csr_from_dense(a: np.ndarray) -> CSR:
    rows, cols = np.nonzero(a)
    return csr_from_coo(rows, cols, a[rows, cols], a.shape, sum_dups=False)


# --------------------------------------------------------------------------
# Edge-batch deltas: incremental CSR updates for dynamic graphs
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CSRDelta:
    """A batch of edge mutations against one CSR operand.

    Records are applied in order (last write to a coordinate wins):
    ``delete[e]`` removes ``(rows[e], cols[e])`` if present (``vals[e]`` is
    ignored), otherwise the record upserts — overwriting an existing entry's
    value or inserting a new structural nonzero.
    """

    rows: np.ndarray      # (e,) int64
    cols: np.ndarray      # (e,) int64
    vals: np.ndarray      # (e,) value per record (ignored for deletes)
    delete: np.ndarray    # (e,) bool

    def __post_init__(self):
        object.__setattr__(self, "rows", np.asarray(self.rows, np.int64))
        object.__setattr__(self, "cols", np.asarray(self.cols, np.int64))
        object.__setattr__(self, "vals", np.asarray(self.vals))
        object.__setattr__(self, "delete", np.asarray(self.delete, bool))
        n = len(self.rows)
        if not (len(self.cols) == len(self.vals) == len(self.delete) == n):
            raise ValueError("CSRDelta fields must have equal length")

    @classmethod
    def upserts(cls, rows, cols, vals) -> "CSRDelta":
        rows = np.asarray(rows, np.int64)
        return cls(rows, cols, vals, np.zeros(len(rows), bool))

    @classmethod
    def deletes(cls, rows, cols) -> "CSRDelta":
        rows = np.asarray(rows, np.int64)
        return cls(rows, cols, np.zeros(len(rows), np.float32),
                   np.ones(len(rows), bool))

    @classmethod
    def concat(cls, deltas: Sequence["CSRDelta"]) -> "CSRDelta":
        return cls(np.concatenate([d.rows for d in deltas]),
                   np.concatenate([d.cols for d in deltas]),
                   np.concatenate([d.vals for d in deltas]),
                   np.concatenate([d.delete for d in deltas]))

    @property
    def changed_rows(self) -> np.ndarray:
        return np.unique(self.rows)

    def __len__(self) -> int:
        return len(self.rows)


@dataclasses.dataclass(frozen=True)
class DeltaResult:
    """Outcome of ``apply_csr_delta``: the post-delta CSR, which rows
    changed, whether the sparsity structure survived (values-only delta),
    and the incrementally-maintained delta signature."""

    csr: CSR
    changed_rows: np.ndarray   # sorted unique rows any record touched
    values_only: bool          # True iff no row's column set changed
    signature: tuple           # incremental_signature(csr), updated in O(Δ)


_ISIG_MASK = (1 << 64) - 1


def _row_sig(i: int, cols: np.ndarray) -> int:
    """Salted 64-bit hash of one row's column set (order-insensitive XOR
    combination across rows stays collision-resistant because the row index
    salts the CRC and a splitmix finalizer spreads it to 64 bits)."""
    crc = zlib.crc32(np.ascontiguousarray(cols, dtype=np.int64).tobytes(),
                     zlib.crc32(np.int64(i).tobytes()))
    z = (crc + 0x9E3779B97F4A7C15) & _ISIG_MASK
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _ISIG_MASK
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _ISIG_MASK
    return (z ^ (z >> 31)) & _ISIG_MASK


def incremental_signature(x: CSR) -> tuple:
    """Delta-maintainable structural identity: XOR of salted per-row hashes.

    Unlike ``planner.structure_signature`` (a whole-array CRC that any
    change recomputes from scratch), this form updates in O(changed rows):
    ``new = old ^ H(old changed rows) ^ H(new changed rows)``.  Equal
    signatures => equal sparsity structure (up to hash collision).
    """
    acc = 0
    for i in range(x.shape[0]):
        s, e = x.indptr[i], x.indptr[i + 1]
        acc ^= _row_sig(i, x.indices[s:e])
    return ("icsr", x.shape, x.nnz, acc)


def apply_csr_delta(a: CSR, delta: CSRDelta,
                    old_signature: Optional[tuple] = None) -> DeltaResult:
    """Apply an edge batch functionally: a new CSR sharing the unchanged
    rows' entries, the changed-row set, and the delta signature updated
    incrementally from ``old_signature`` (recomputed when absent).
    """
    m, n = a.shape
    if len(delta) and (delta.rows.min() < 0 or delta.rows.max() >= m
                       or delta.cols.min() < 0 or delta.cols.max() >= n):
        raise ValueError(f"delta coordinates outside shape {a.shape}")
    changed = delta.changed_rows
    if old_signature is not None and old_signature[:2] != ("icsr", a.shape):
        raise ValueError("old_signature does not match the operand")

    # per changed row: fold the record stream into the existing entries
    new_rows_cols: dict = {}
    new_rows_vals: dict = {}
    values_only = True
    for r in changed:
        cols0, vals0 = a.row(int(r))
        entries = dict(zip(cols0.tolist(), vals0.tolist()))
        sel = delta.rows == r
        for c, v, dele in zip(delta.cols[sel].tolist(),
                              delta.vals[sel].tolist(),
                              delta.delete[sel].tolist()):
            if dele:
                entries.pop(c, None)
            else:
                entries[c] = v
        cols1 = np.fromiter(sorted(entries), dtype=np.int64,
                            count=len(entries))
        new_rows_cols[int(r)] = cols1
        new_rows_vals[int(r)] = np.array([entries[c] for c in cols1],
                                         dtype=a.data.dtype)
        if values_only and not np.array_equal(cols0, cols1):
            values_only = False

    er = _expand_rows(a.indptr)
    keep = ~np.isin(er, changed)
    all_rows = np.concatenate(
        [er[keep]] + [np.full(len(new_rows_cols[int(r)]), r, np.int64)
                      for r in changed])
    all_cols = np.concatenate(
        [a.indices[keep]] + [new_rows_cols[int(r)] for r in changed])
    all_vals = np.concatenate(
        [a.data[keep]] + [new_rows_vals[int(r)] for r in changed])
    out = csr_from_coo(all_rows, all_cols, all_vals, a.shape, sum_dups=False)
    out.data = out.data.astype(a.data.dtype, copy=False)

    if old_signature is not None:
        acc = old_signature[3]
        for r in changed:
            acc ^= _row_sig(int(r), a.row(int(r))[0])
            acc ^= _row_sig(int(r), new_rows_cols[int(r)])
        sig = ("icsr", a.shape, out.nnz, acc)
    else:
        sig = incremental_signature(out)
    return DeltaResult(csr=out, changed_rows=changed,
                       values_only=values_only, signature=sig)


def bcsr_apply_delta(b: BCSR, new: CSR, changed_rows: np.ndarray) -> BCSR:
    """Update a BCSR mirror of ``new`` after a delta touching
    ``changed_rows``: only the affected block rows' occupancy and blocks
    are rebuilt; every other block row's device blocks are reused.
    """
    bs = b.block_size
    if (b.shape != new.shape):
        raise ValueError("BCSR/CSR shape mismatch")
    changed_rows = np.asarray(changed_rows, np.int64)
    if len(changed_rows) == 0:
        return b
    affected = set(np.unique(changed_rows // bs).tolist())
    mb = b.block_rows

    seg_indices = []   # per block row: occupied block-col indices
    seg_blocks = []    # per block row: host or device (nnzb_i, bs, bs)
    host_blocks = isinstance(b.blocks, np.ndarray)
    for br in range(mb):
        if br not in affected:
            s, e = int(b.indptr[br]), int(b.indptr[br + 1])
            seg_indices.append(b.indices[s:e])
            seg_blocks.append(b.blocks[s:e])
            continue
        lo, hi = br * bs, min((br + 1) * bs, new.shape[0])
        s, e = int(new.indptr[lo]), int(new.indptr[hi])
        rows = _expand_rows(new.indptr)[s:e] - lo
        cols = new.indices[s:e]
        vals = new.data[s:e]
        bcols = np.unique(cols // bs) if len(cols) else \
            np.zeros(0, np.int64)
        blocks = np.zeros((len(bcols), bs, bs),
                          dtype=np.asarray(vals).dtype)
        if len(cols):
            pos = np.searchsorted(bcols, cols // bs)
            blocks[pos, rows, cols % bs] = vals
        seg_indices.append(bcols)
        seg_blocks.append(blocks if host_blocks else jnp.asarray(blocks))

    counts = np.array([len(ix) for ix in seg_indices], np.int64)
    indptr = np.zeros(mb + 1, np.int64)
    indptr[1:] = np.cumsum(counts)
    indices = (np.concatenate(seg_indices) if counts.sum()
               else np.zeros(0, np.int64))
    xp = np if isinstance(b.blocks, np.ndarray) else jnp
    nonempty = [blk for blk in seg_blocks if blk.shape[0]]
    blocks = xp.concatenate(nonempty) if nonempty else b.blocks[:0]
    return BCSR(indptr, indices.astype(np.int64), blocks, b.shape, bs)


# --------------------------------------------------------------------------
# Device-side PaddedCSR (ELL): rows padded to a static width
# --------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PaddedCSR:
    """ELL-style padded rows: cols:(m, w) int32, vals:(m, w), lens:(m,) int32.

    Padding columns hold ``ncols`` (an out-of-range sentinel that sorts after
    every real column, which keeps merge-based algorithms branch-free).
    """

    cols: Array  # (m, w) int32, sorted ascending per row, pad = ncols
    vals: Array  # (m, w)
    lens: Array  # (m,) int32
    shape: Tuple[int, int]  # static

    def tree_flatten(self):
        return (self.cols, self.vals, self.lens), self.shape

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, shape=aux)

    @property
    def width(self) -> int:
        return self.cols.shape[1]

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def ncols(self) -> int:
        return self.shape[1]

    def valid(self) -> Array:
        return self.cols < self.shape[1]

    def to_dense(self) -> Array:
        m, n = self.shape
        out = jnp.zeros((m, n + 1), dtype=self.vals.dtype)
        rows = jnp.broadcast_to(jnp.arange(m)[:, None], self.cols.shape)
        out = out.at[rows, self.cols].add(jnp.where(self.valid(), self.vals, 0))
        return out[:, :n]


def padded_from_csr(a: CSR, width: Optional[int] = None, dtype=jnp.float32) -> PaddedCSR:
    a = a.sorted_rows()
    m, n = a.shape
    row_nnz = a.row_nnz()
    w = int(width if width is not None else max(1, int(row_nnz.max(initial=0))))
    cols = np.full((m, w), n, dtype=np.int32)
    vals = np.zeros((m, w), dtype=np.float32)
    # vectorized scatter: slot of entry e is its offset within its row;
    # entries beyond the requested width are dropped (same as the old
    # per-row loop, without the per-row Python cost)
    rows = _expand_rows(a.indptr)
    slots = np.arange(a.nnz, dtype=np.int64) - a.indptr[rows]
    keep = slots < w
    cols[rows[keep], slots[keep]] = a.indices[keep]
    vals[rows[keep], slots[keep]] = a.data[keep]
    return PaddedCSR(
        to_device(cols), to_device(vals, dtype),
        to_device(np.minimum(row_nnz, w), jnp.int32), (m, n)
    )


def padded_from_dense(a: np.ndarray, width: Optional[int] = None) -> PaddedCSR:
    return padded_from_csr(csr_from_dense(np.asarray(a)), width)


# --------------------------------------------------------------------------
# Block-CSR: the TPU-native format.  Tiles are dense (bs x bs) blocks.
# --------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BCSR:
    """Block-CSR: indptr:(Mb+1,), indices:(nnzb,), blocks:(nnzb, bs, bs),
    or the block kernel's stacked operand (``bcsr_from_csr(pattern=True)``).

    ``indptr``/``indices`` live on host (numpy) because they drive schedule
    construction (the symbolic phase); ``blocks`` is a device array.
    """

    indptr: np.ndarray  # host
    indices: np.ndarray  # host, sorted per block-row
    blocks: Array  # (nnzb, bs, bs) device
    shape: Tuple[int, int]  # element shape
    block_size: int

    def tree_flatten(self):
        return (self.blocks,), (self.indptr.tobytes(), self.indices.tobytes(),
                                len(self.indptr), len(self.indices),
                                self.shape, self.block_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        pb, ib, np_len, ni_len, shape, bs = aux
        indptr = np.frombuffer(pb, dtype=np.int64, count=np_len)
        indices = np.frombuffer(ib, dtype=np.int64, count=ni_len)
        return cls(indptr, indices, children[0], shape, bs)

    @property
    def nnzb(self) -> int:
        return int(self.indices.shape[0])

    @property
    def block_rows(self) -> int:
        return len(self.indptr) - 1

    @property
    def block_cols(self) -> int:
        return -(-self.shape[1] // self.block_size)

    def block_row(self, i: int) -> np.ndarray:
        return self.indices[self.indptr[i]: self.indptr[i + 1]]

    def to_dense(self) -> np.ndarray:
        bs = self.block_size
        mb, nb = self.block_rows, self.block_cols
        out = np.zeros((mb * bs, nb * bs), dtype=np.asarray(self.blocks).dtype)
        blocks = np.asarray(self.blocks)
        for i in range(mb):
            for p in range(self.indptr[i], self.indptr[i + 1]):
                j = self.indices[p]
                out[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs] = blocks[p]
        return out[: self.shape[0], : self.shape[1]]


def bcsr_from_dense(a: np.ndarray, block_size: int, prune_zero: bool = True) -> BCSR:
    a = np.asarray(a)
    m, n = a.shape
    bs = block_size
    mb, nb = -(-m // bs), -(-n // bs)
    padded = np.zeros((mb * bs, nb * bs), dtype=a.dtype)
    padded[:m, :n] = a
    tiles = padded.reshape(mb, bs, nb, bs).transpose(0, 2, 1, 3)
    nz = np.abs(tiles).sum(axis=(2, 3)) != 0 if prune_zero else np.ones((mb, nb), bool)
    rows, cols = np.nonzero(nz)
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    indptr = np.zeros(mb + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    indptr = np.cumsum(indptr)
    blocks = tiles[rows, cols] if len(rows) else np.zeros((0, bs, bs), a.dtype)
    return BCSR(indptr, cols.astype(np.int64), jnp.asarray(blocks), (m, n), bs)


def bcsr_from_csr(a: CSR, block_size: int, dtype=None,
                  pattern: bool = False) -> BCSR:
    """Direct CSR -> BCSR: scatter entries into only the occupied blocks.

    Never materializes the dense matrix — memory is O(nnzb * bs^2), bounded
    by the input's block structure, which is what makes the tile path usable
    at scales where an (m, n) densify would not fit.  Rows/cols beyond the
    last full block are padded into partial edge blocks (zero filled), same
    layout as ``bcsr_from_dense``.  Assumes ``a`` has no duplicate entries
    (every ``csr_from_coo``-built CSR satisfies this).

    With ``pattern`` the blocks are the block kernel's stacked operand,
    ``(nnzb, 2, rows, lanes)`` with each block stored as
    ``kernel.block_layout(bs)`` says (lane-dense for bs 32 and 64, so the
    kernel reads them without a padded copy): plane 0 the values, plane 1
    1.0 at every stored entry (an explicitly stored 0.0 included), both
    scattered into one host array and copied to the device once
    (``ops.block_spgemm_with_structure``).
    """
    bs = block_size
    m, n = a.shape
    mb, nb = -(-m // bs), -(-n // bs)
    with obs.span("spgemm.bcsr", bs=bs) as sp:
        rows = _expand_rows(a.indptr)
        cols = a.indices
        key = (rows // bs) * nb + cols // bs
        uniq, inv = np.unique(key, return_inverse=True)
        sp.set(nnzb=len(uniq))
        r, c = rows % bs, cols % bs
        if pattern:
            from repro.kernels.masked_matmul.kernel import (block_layout,
                                                            block_position)
            blocks = np.zeros((len(uniq), 2) + block_layout(bs),
                              dtype=a.data.dtype)
            r, c = block_position(r, c, bs)
            blocks[inv, 0, r, c] = a.data
            blocks[inv, 1, r, c] = 1
        else:
            blocks = np.zeros((len(uniq), bs, bs), dtype=a.data.dtype)
            blocks[inv, r, c] = a.data
        ubr, ubc = uniq // nb, uniq % nb
        indptr = np.zeros(mb + 1, dtype=np.int64)
        np.add.at(indptr, ubr + 1, 1)
        dev = to_device(blocks, dtype)
        return BCSR(np.cumsum(indptr), ubc.astype(np.int64), dev, (m, n),
                    bs)


def bcsr_to_csr(a: BCSR, prune_zero: bool = True) -> CSR:
    """Inverse of ``bcsr_from_csr``: element CSR of the stored blocks.

    With ``prune_zero`` (default) only numerically nonzero elements are
    kept — the result-extraction contract of the tile pipeline, where the
    output's element structure is the nonzeros the masked product actually
    produced.  Elements in the zero-padded edge region (beyond ``shape``)
    are always dropped.
    """
    bs = a.block_size
    m, n = a.shape
    blocks = np.asarray(a.blocks)
    brow = np.repeat(np.arange(a.block_rows, dtype=np.int64),
                     np.diff(a.indptr))
    if prune_zero:
        p, r, c = np.nonzero(blocks)
    else:
        p, r, c = (x.ravel() for x in np.indices(blocks.shape))
    rows = brow[p] * bs + r
    cols = a.indices[p] * bs + c
    keep = (rows < m) & (cols < n)
    return csr_from_coo(rows[keep], cols[keep], blocks[p, r, c][keep],
                        (m, n), sum_dups=False)


def bcsr_block_positions(a: BCSR, bi: np.ndarray, bj: np.ndarray
                         ) -> np.ndarray:
    """Positions in ``a.blocks`` of blocks (bi[t], bj[t]); -1 when absent.

    Relies on the BCSR invariant that blocks are stored in row-major
    (block-row, block-col) order, so a single searchsorted resolves every
    query.
    """
    nb = a.block_cols
    brow = np.repeat(np.arange(a.block_rows, dtype=np.int64),
                     np.diff(a.indptr))
    keys = brow * nb + a.indices
    q = np.asarray(bi, dtype=np.int64) * nb + np.asarray(bj, dtype=np.int64)
    pos = np.searchsorted(keys, q)
    pos_c = np.minimum(pos, max(0, len(keys) - 1))
    ok = (pos < len(keys)) & (keys[pos_c] == q) if len(keys) else \
        np.zeros(len(q), dtype=bool)
    return np.where(ok, pos, -1)


def bcsr_structure_transpose(a: BCSR) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Column-major view of the block structure: (indptr_T, rows_T, pos_T).

    ``pos_T[p]`` is the position in ``a.blocks`` of the p-th block when
    traversing column-by-column.  Used to build pull-based schedules.
    """
    mb = a.block_rows
    nb = a.block_cols
    rows = np.repeat(np.arange(mb, dtype=np.int64), np.diff(a.indptr))
    cols = a.indices
    pos = np.arange(a.nnzb, dtype=np.int64)
    order = np.lexsort((rows, cols))
    rows_t, cols_t, pos_t = rows[order], cols[order], pos[order]
    indptr_t = np.zeros(nb + 1, dtype=np.int64)
    np.add.at(indptr_t, cols_t + 1, 1)
    return np.cumsum(indptr_t), rows_t, pos_t


# --------------------------------------------------------------------------
# BCSR panel helpers (distributed ring-SUMMA: row-panels and K-slabs)
# --------------------------------------------------------------------------


def bcsr_pad_block_rows(a: BCSR, target_block_rows: int) -> BCSR:
    """Append empty block rows so ``a`` has exactly ``target_block_rows``.

    The element shape grows with the padding (the new rows are structurally
    empty), so downstream panel splits see equal shards.
    """
    mb = a.block_rows
    if target_block_rows < mb:
        raise ValueError(f"cannot shrink {mb} block rows to "
                         f"{target_block_rows}")
    if target_block_rows == mb:
        return a
    indptr = np.concatenate([
        a.indptr,
        np.full(target_block_rows - mb, a.indptr[-1], dtype=a.indptr.dtype)])
    return BCSR(indptr, a.indices, a.blocks,
                (target_block_rows * a.block_size, a.shape[1]), a.block_size)


def bcsr_row_panels(a: BCSR, nparts: int) -> Tuple[BCSR, ...]:
    """Split ``a`` into ``nparts`` equal block-row panels.

    Requires ``a.block_rows % nparts == 0`` (pad first via
    ``bcsr_pad_block_rows``).  Each panel's ``indptr`` is rebased to start
    at 0 and its ``blocks`` is the contiguous device slice of the parent's
    blocks, so panel-local schedule positions index the panel directly.
    """
    mb = a.block_rows
    if mb % nparts:
        raise ValueError(f"{mb} block rows do not split into {nparts} panels")
    rows_per = mb // nparts
    out = []
    for d in range(nparts):
        lo, hi = d * rows_per, (d + 1) * rows_per
        s, e = int(a.indptr[lo]), int(a.indptr[hi])
        out.append(BCSR(a.indptr[lo:hi + 1] - a.indptr[lo],
                        a.indices[s:e], a.blocks[s:e],
                        (rows_per * a.block_size, a.shape[1]),
                        a.block_size))
    return tuple(out)


def bcsr_concat_row_panels(panels: Sequence[BCSR]) -> BCSR:
    """Inverse of ``bcsr_row_panels``: stack block-row panels vertically."""
    if not panels:
        raise ValueError("no panels")
    bs = panels[0].block_size
    ncols = panels[0].shape[1]
    indptrs = [panels[0].indptr]
    offset = panels[0].indptr[-1]
    for p in panels[1:]:
        assert p.block_size == bs and p.shape[1] == ncols
        indptrs.append(p.indptr[1:] + offset)
        offset = offset + p.indptr[-1]
    xp = np if all(isinstance(p.blocks, np.ndarray) for p in panels) else jnp
    blocks = (xp.concatenate([p.blocks for p in panels])
              if sum(p.nnzb for p in panels)
              else panels[0].blocks[:0])
    return BCSR(np.concatenate(indptrs),
                np.concatenate([p.indices for p in panels]),
                blocks,
                (sum(p.shape[0] for p in panels), ncols), bs)


def pad_panel_blocks(blocks: Array, target_nnzb: int) -> Array:
    """Pad a (nnzb, bs, bs) block array with zero blocks to ``target_nnzb``
    (>= 1), giving every ring participant one static ``ppermute`` shape.
    Works on device or host (numpy) blocks without changing residency."""
    xp = np if isinstance(blocks, np.ndarray) else jnp
    nnzb = blocks.shape[0]
    target = max(1, target_nnzb)
    if nnzb == target:
        return blocks
    pad = xp.zeros((target - nnzb,) + tuple(blocks.shape[1:]), blocks.dtype)
    return xp.concatenate([blocks, pad]) if nnzb else pad


# --------------------------------------------------------------------------
# Random sparse generators (paper Sec. 7: Erdos-Renyi and R-MAT/Graph500)
# --------------------------------------------------------------------------


def erdos_renyi(n: int, avg_degree: float, seed: int = 0,
                values: str = "uniform") -> CSR:
    """ER(n, d): each row has ~Poisson(d) nonzeros at uniform columns."""
    rng = np.random.default_rng(seed)
    nnz = rng.poisson(avg_degree, size=n)
    rows = np.repeat(np.arange(n, dtype=np.int64), nnz)
    cols = rng.integers(0, n, size=int(nnz.sum()), dtype=np.int64)
    if values == "ones":
        vals = np.ones(len(rows), dtype=np.float32)
    else:
        vals = rng.uniform(0.5, 1.5, size=len(rows)).astype(np.float32)
    return csr_from_coo(rows, cols, vals, (n, n))


def rmat(scale: int, edge_factor: int = 16, seed: int = 0,
         a: float = 0.57, b: float = 0.19, c: float = 0.19,
         symmetric: bool = True, remove_self_loops: bool = True) -> CSR:
    """R-MAT generator with Graph500 parameters (a,b,c,d)=(.57,.19,.19,.05)."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    rows = np.zeros(m, dtype=np.int64)
    cols = np.zeros(m, dtype=np.int64)
    for lvl in range(scale):
        r = rng.random(m)
        # quadrant probabilities with noise, Graph500-style
        ab = a + b
        abc = a + b + c
        go_right = ((r >= a) & (r < ab)) | (r >= abc)
        go_down = r >= ab
        rows |= go_down.astype(np.int64) << lvl
        cols |= go_right.astype(np.int64) << lvl
    if remove_self_loops:
        keep = rows != cols
        rows, cols = rows[keep], cols[keep]
    if symmetric:
        rows, cols = np.concatenate([rows, cols]), np.concatenate([cols, rows])
    vals = np.ones(len(rows), dtype=np.float32)
    out = csr_from_coo(rows, cols, vals, (n, n))
    out.data[:] = 1.0  # binarize: duplicate edges must not create weights
    return out


def random_mask_like(a: CSR, keep_prob: float, seed: int = 0) -> CSR:
    """Random subsample of a's pattern (mask values are irrelevant)."""
    rng = np.random.default_rng(seed)
    keep = rng.random(a.nnz) < keep_prob
    rows = _expand_rows(a.indptr)[keep]
    return csr_from_coo(rows, a.indices[keep], np.ones(keep.sum(), np.float32),
                        a.shape, sum_dups=False)


def er_mask(n: int, d: float, seed: int) -> CSR:
    """ER-pattern mask: ~Poisson(d) ones per row at uniform columns.

    The mask family of the paper's Fig. 7 density sweep; shared by the
    benchmarks and the calibration probes so both measure the same
    distribution.
    """
    rng = np.random.default_rng(seed)
    nnz = rng.poisson(d, size=n)
    rows = np.repeat(np.arange(n, dtype=np.int64), nnz)
    cols = rng.integers(0, n, size=int(nnz.sum()), dtype=np.int64)
    return csr_from_coo(rows, cols, np.ones(len(rows), np.float32), (n, n))


def block_sparse(n: int, bs: int, tile_density: float,
                 within_density: float, seed: int,
                 mask: bool = False) -> np.ndarray:
    """Block-structured sparse matrix as a DENSE (n, n) float32 array:
    (bs x bs) tiles occupied w.p. ``tile_density``, elements inside an
    occupied tile w.p. ``within_density``; integer values in [1, 5)
    unless ``mask`` (then 0/1).

    The tile/ring routes' calibration family; shared by bench_tile,
    bench_dist, and the tuning probes — the draw order is part of the
    committed grids' identity, so change it only with a regeneration.
    """
    rng = np.random.default_rng(seed)
    nb = n // bs
    tiles = rng.random((nb, nb)) < tile_density
    if not tiles.any():
        tiles[0, 0] = True
    dense = np.kron(tiles, np.ones((bs, bs))) * (rng.random((n, n))
                                                 < within_density)
    if mask:
        return dense.astype(np.float32)
    return (dense * rng.integers(1, 5, (n, n))).astype(np.float32)


def tril(a: CSR, strict: bool = True) -> CSR:
    with obs.span("graph.tril"):
        rows = _expand_rows(a.indptr)
        keep = a.indices < rows if strict else a.indices <= rows
        return csr_from_coo(rows[keep], a.indices[keep], a.data[keep],
                            a.shape, sum_dups=False)
