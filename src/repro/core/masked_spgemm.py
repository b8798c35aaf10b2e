"""Row-parallel Masked SpGEMM drivers (paper Sec. 5-6).

``masked_spgemm`` computes  C = M (.) (A B)  (or the complemented variant)
by vmapping the row-level accumulator kernels over rows of A/M, exactly like
the paper's OpenMP parallel-for over output rows.  One- vs two-phase:

  * 1P: numeric pass only; the output is allocated at the mask's size
        (output pattern is a subset of the mask pattern), matching the
        paper's observation that the mask bounds the output.
  * 2P: a symbolic pass first computes per-row output nnz; the numeric pass
        then writes into an exactly-sized allocation.  Here the symbolic
        pass is real work (it is timed by the benchmark harness) while the
        "allocation" difference shows up as the tighter padded width.

Outputs are returned mask-aligned: ``vals[i, p]`` / ``present[i, p]`` refer
to the p-th nonzero slot of mask row i (stable, sorted by construction).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.masked_matmul.kernel import block_position

from . import accumulators as acc
from .formats import (CSR, PaddedCSR, padded_from_csr, csr_from_coo,
                      bcsr_from_csr, bcsr_block_positions, _expand_rows,
                      to_device)
from .semiring import Semiring, PLUS_TIMES

#: the vmapped row kernels; the BCSR tile route ("tile") dispatches through
#: the Pallas/XLA block executors instead and is planner- or caller-elected
ALGORITHMS = ("msa", "hash", "mca", "heap", "heapdot", "inner")


@dataclasses.dataclass(frozen=True)
class MaskedSpGEMMResult:
    vals: jax.Array      # (m, pm) mask-aligned values
    present: jax.Array   # (m, pm) bool
    mask_cols: jax.Array  # (m, pm) int32 column ids (pad = n)
    shape: Tuple[int, int]

    def to_dense(self):
        m, n = self.shape
        rows = jnp.broadcast_to(jnp.arange(m)[:, None], self.mask_cols.shape)
        out = jnp.zeros((m, n + 1), self.vals.dtype)
        cols = jnp.where(self.present, self.mask_cols, n)
        out = out.at[rows, cols].set(jnp.where(self.present, self.vals, 0))
        return out[:, :n]

    def to_csr(self) -> CSR:
        present = np.asarray(self.present)
        rows, slots = np.nonzero(present)
        cols = np.asarray(self.mask_cols)[rows, slots]
        vals = np.asarray(self.vals)[rows, slots]
        return csr_from_coo(rows, cols, vals, self.shape, sum_dups=False)

    @property
    def nnz(self):
        return jnp.sum(self.present.astype(jnp.int32))


def _row_fn(algorithm: str, n: int, kdim: int, sr: Semiring,
            complement: bool, n_inspect: int):
    if algorithm == "msa":
        def f(mc, ac, av, al, Bc, Bv, Bl):
            return acc.msa_row(mc, ac, av, al, Bc, Bv, Bl, n, kdim, sr,
                               complement=complement)
    elif algorithm == "hash":
        if complement:
            raise NotImplementedError(
                "hash complement: use msa (dense states) per paper Sec. 5.2")
        def f(mc, ac, av, al, Bc, Bv, Bl):
            return acc.hash_row(mc, ac, av, al, Bc, Bv, Bl, n, kdim, sr)
    elif algorithm == "mca":
        if complement:
            raise NotImplementedError("MCA does not support complemented "
                                      "masks (paper Sec. 8.4)")
        def f(mc, ac, av, al, Bc, Bv, Bl):
            return acc.mca_row(mc, ac, av, al, Bc, Bv, Bl, n, kdim, sr)
    elif algorithm in ("heap", "heapdot"):
        ni = 1 if algorithm == "heap" else (0 if complement else 10 ** 9)
        ni = n_inspect if n_inspect is not None else ni
        def f(mc, ac, av, al, Bc, Bv, Bl):
            return acc.heap_row(mc, ac, av, al, Bc, Bv, Bl, n, kdim, sr,
                                n_inspect=ni, complement=complement)
    elif algorithm == "inner":
        if complement:
            raise NotImplementedError("inner requires an explicit mask")
        def f(mc, ac, av, al, Btc, Btv, Btl):
            return acc.inner_row(mc, ac, av, al, Btc, Btv, Btl, n, kdim, sr)
    else:
        raise ValueError(f"unknown algorithm {algorithm!r}")
    return f


@functools.partial(
    jax.jit,
    static_argnames=("algorithm", "sr", "complement", "n_inspect", "shape",
                     "kdim"))
def _masked_spgemm_padded(M: PaddedCSR, A: PaddedCSR, B_or_Bt: PaddedCSR,
                          *, algorithm: str, sr: Semiring, complement: bool,
                          n_inspect: Optional[int], shape, kdim):
    n = shape[1]
    row = _row_fn(algorithm, n, kdim, sr, complement, n_inspect)
    f = jax.vmap(
        lambda mc, ac, av, al: row(mc, ac, av, al, B_or_Bt.cols,
                                   B_or_Bt.vals, B_or_Bt.lens))
    return f(M.cols, A.cols, A.vals, A.lens)


def masked_spgemm(A, B, M, *, algorithm: str = "auto",
                  semiring: Semiring = PLUS_TIMES, complement: bool = False,
                  two_phase: bool = False, n_inspect: Optional[int] = None,
                  widths: Optional[Tuple[int, int, int]] = None,
                  tile_block: Optional[int] = None, plan=None,
                  devices: int = 1):
    """C = M (.) (A B)   [or  C = (not M) (.) (A B)].

    A, B, M: host CSR (or PaddedCSR already on device).  Returns a
    MaskedSpGEMMResult (mask-aligned) for the normal mask; for the
    complemented mask returns (dense_vals, dense_present) since the output
    is not a subset of the mask pattern.

    ``algorithm="auto"`` (the default) consults the planner: cheap
    structural statistics pick the cheapest kernel per the paper's Sec. 7-8
    guidelines, memoized by structural signature (plus the active
    cost-model token — retuning or activating a calibration profile via
    ``repro.tuning`` / ``python -m repro.tune`` re-plans everything) so
    repeated shapes skip re-planning.  When the plan elects the BCSR tile route
    (``plan.algorithm == "tile"``), the product executes on the block
    executors (Pallas on TPU, compiled XLA elsewhere) end to end — no
    densify anywhere on that path.  ``algorithm="tile"`` forces the tile
    route (``tile_block`` picks the block size; plus_times, explicit mask,
    host-CSR operands only).  A precomputed ``plan`` (from
    ``planner.plan``) overrides ``algorithm`` and ``widths``.

    ``devices > 1`` runs the product on a 1-D mesh over the first
    ``devices`` devices through ``distributed_masked_spgemm``:
    ``algorithm`` is ``"ring"`` (the sparse BCSR ring, ``tile_block`` its
    block size), ``"row"`` or ``"auto"``; host CSR operands only.  It
    raises where the mesh path cannot honour a request: ``two_phase``, a
    plan or widths, a complemented mask, another algorithm, and the ring
    under a semiring other than plus_times.
    """
    m, k = A.shape
    k2, n = B.shape
    assert k == k2, (A.shape, B.shape)
    if devices != 1:
        if two_phase or plan is not None or widths is not None:
            raise NotImplementedError(
                "two_phase, plan and widths are single-device options")
        from .distributed import device_mesh, distributed_masked_spgemm
        return distributed_masked_spgemm(
            A, B, M, device_mesh(devices), algorithm=algorithm,
            semiring=semiring, complement=complement, block_size=tile_block)
    if two_phase and algorithm == "tile":
        # the tile route's symbolic phase is the host schedule build; a 2P
        # padded-width pass has no meaning there, and silently ignoring the
        # request would misreport what was measured
        raise NotImplementedError(
            "two_phase is not supported by the tile route (its symbolic "
            "phase is the host schedule build); use a row algorithm")
    if plan is None and algorithm == "auto":
        from .planner import plan as _plan
        plan = _plan(A, B, M, complement=complement, semiring=semiring)
    if plan is not None:
        algorithm = plan.algorithm
        if algorithm == "tile" and two_phase:
            # an auto-elected tile route cannot honor two_phase: fall back
            # to the cheapest row kernel from the same plan's ranking
            algorithm = next(name for name, _ in plan.costs
                             if name != "tile")
            s = plan.stats
            if widths is None:
                widths = (s.wa, s.wbt if algorithm == "inner" else s.wb,
                          s.pm)
        if widths is None:
            widths = plan.widths
        if n_inspect is None:
            n_inspect = plan.n_inspect
        if tile_block is None and plan.tile_block:
            tile_block = plan.tile_block
    wa, wb, wm = widths or (None, None, None)

    if algorithm == "tile":
        from repro.kernels.masked_matmul.ops import tile_path_supported
        if not tile_path_supported(semiring.name, complement):
            raise NotImplementedError(
                "tile route requires plus_times and an explicit mask")
        if not (isinstance(A, CSR) and isinstance(B, CSR)
                and isinstance(M, CSR)):
            raise NotImplementedError("tile route needs host CSR operands")
        return _masked_spgemm_tile(A, B, M, block_size=tile_block, wm=wm)

    with obs.span("spgemm.host_prep", algorithm=algorithm):
        A_p = A if isinstance(A, PaddedCSR) else padded_from_csr(A, wa)
        M_p = M if isinstance(M, PaddedCSR) else padded_from_csr(M, wm)
        if algorithm == "inner":
            Bt = B.transpose() if isinstance(B, CSR) else B
            B_p = (Bt if isinstance(Bt, PaddedCSR)
                   else padded_from_csr(Bt, wb))
        else:
            B_p = (B if isinstance(B, PaddedCSR)
                   else padded_from_csr(B, wb))

    if two_phase:
        # symbolic pass: exact output structure (counts); in this padded
        # setting its product is the tight numeric width.  The symbolic pass
        # always walks B row-major, so Inner (which multiplies against B^T)
        # pads a row-major copy just for this phase.
        if algorithm == "inner":
            B_sym = B if isinstance(B, PaddedCSR) else padded_from_csr(B, wb)
        else:
            B_sym = B_p
        counts = symbolic_phase(A_p, M_p, B_sym, shape=(m, n), kdim=k)
        _ = counts.block_until_ready()

    attrs = {"intersect": "compare_all"} if algorithm == "inner" else {}
    with obs.span("spgemm.row", algorithm=algorithm, m=m, n=n, **attrs):
        vals, present = _masked_spgemm_padded(
            M_p, A_p, B_p, algorithm=algorithm, sr=semiring,
            complement=complement, n_inspect=n_inspect, shape=(m, n),
            kdim=k)
    if complement:
        return vals, present
    return MaskedSpGEMMResult(vals, present, M_p.cols, (m, n))


@functools.partial(jax.jit, static_argnames=("shape", "kdim"))
def symbolic_phase(A: PaddedCSR, M: PaddedCSR, B: Optional[PaddedCSR], *,
                   shape, kdim):
    """Two-phase symbolic pass: per-row output nnz (paper Sec. 6)."""
    n = shape[1]
    f = jax.vmap(lambda mc, ac, al: acc.symbolic_row(
        mc, ac, al, B.cols, B.lens, n, kdim))
    return f(M.cols, A.cols, A.lens)


# ---------------------------------------------------------------------------
# BCSR tile route: block executors end-to-end, densify-free
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("m", "pm"))
def _tile_gather(blocks, pos, roff, coff, rows, slots, *, m, pm):
    """Gather per-mask-element values (plane 0) and structural counts
    (plane 1) out of the stacked block result and scatter them into the
    mask-aligned (m, pm) layout."""
    vals_flat = blocks[pos, 0, roff, coff]
    cnt_flat = blocks[pos, 1, roff, coff]
    vals = jnp.zeros((m, pm), blocks.dtype)
    vals = vals.at[rows, slots].set(vals_flat, mode="drop")
    present = jnp.zeros((m, pm), bool)
    present = present.at[rows, slots].set(cnt_flat > 0, mode="drop")
    return vals, present


def _masked_spgemm_tile(A: CSR, B: CSR, M: CSR, *,
                        block_size: Optional[int] = None,
                        wm: Optional[int] = None,
                        interpret=None, backend=None) -> MaskedSpGEMMResult:
    """Execute C = M (.) (A B) on the BCSR tile pipeline.

    Densify-free end to end: CSR operands scatter into occupied blocks
    (``bcsr_from_csr``), the vectorized host schedule replays on the block
    executor, and the result is gathered straight from the output blocks
    into the same mask-aligned layout the row kernels produce.  Each
    operand's values and stored-entry pattern are stacked as two planes of
    one block array, so one walk of the schedule computes the values and
    the structural counts ``present`` comes from: exact element-level
    structure — bitwise the row kernels' semantics, including
    numeric-cancellation cases and explicitly stored zeros.
    """
    from repro.kernels.masked_matmul.ops import (block_spgemm_with_structure,
                                                 resolve_executor)

    m, k = A.shape
    _, n = B.shape
    if M.nnz == 0:
        M_p = padded_from_csr(M, wm)
        z = jnp.zeros((m, M_p.width), jnp.float32)
        return MaskedSpGEMMResult(z, jnp.zeros((m, M_p.width), bool),
                                  M_p.cols, (m, n))
    if block_size is None:
        from .planner import ring_block_candidates
        block_size = ring_block_candidates(m, k, n)[0]
    bs = block_size
    backend, interpret = resolve_executor(backend, interpret)
    with obs.span("spgemm.tile", block=bs, m=m, n=n, executor=backend,
                  interpret=interpret):
        with obs.span("spgemm.host_prep", algorithm="tile"):
            Ab = bcsr_from_csr(A, bs, pattern=True)
            Bb = bcsr_from_csr(B, bs, pattern=True)
            Mb = bcsr_from_csr(M, bs)
        Cb = block_spgemm_with_structure(Ab, Bb, Mb, interpret=interpret,
                                         backend=backend)
        return gather_mask_aligned(M, Mb, Cb.blocks, n=n, wm=wm)


def gather_mask_aligned(M: CSR, Mb_struct, blocks, *, n: int,
                        wm: Optional[int] = None) -> MaskedSpGEMMResult:
    """Extract a mask-aligned result from block-granular values/counts.

    ``blocks`` is a ``(nnzb, 2, *block_layout(bs))`` device array, values
    in plane 0 and structural counts in plane 1, laid out in
    ``Mb_struct``'s block order (the 1P allocation: output structure ==
    mask block structure).  The distributed ring does NOT come through
    here — its extraction is panel-local inside the shard program.
    """
    m = M.shape[0]
    bs = Mb_struct.block_size
    with obs.span("spgemm.gather"):
        M_p = padded_from_csr(M, wm)
        # host-side addressing: every mask element lives in a mask block
        # by construction
        mr = _expand_rows(M.indptr)
        mc = M.indices
        pos = bcsr_block_positions(Mb_struct, mr // bs, mc // bs)
        slots = np.arange(M.nnz, dtype=np.int64) - M.indptr[mr]
        roff, coff = block_position(mr % bs, mc % bs, bs)
        addr = [to_device(x) for x in (pos, roff, coff, mr, slots)]
    vals, present = _tile_gather(blocks, *addr, m=m, pm=M_p.width)
    return MaskedSpGEMMResult(vals, present, M_p.cols, (m, n))


# ---------------------------------------------------------------------------
# Batched driver: one plan + one compiled program for same-shape operands
# ---------------------------------------------------------------------------


def _stack_padded(mats, width: int) -> PaddedCSR:
    """Pad each CSR to ``width`` and stack into a batched PaddedCSR whose
    leaves carry a leading batch dim (vmap slices it back off).

    Host-CSR batches are padded into ONE host array per leaf and
    transferred once — stacking per-element device arrays costs a
    dispatch per element, which is exactly the overhead batching exists
    to remove (the serving engine's hot path)."""
    if all(isinstance(m, CSR) for m in mats):
        b = len(mats)
        m_rows, n = mats[0].shape
        cols = np.full((b, m_rows, width), n, dtype=np.int32)
        vals = np.zeros((b, m_rows, width), dtype=np.float32)
        lens = np.zeros((b, m_rows), dtype=np.int32)
        for i, mat in enumerate(mats):
            mat = mat.sorted_rows()
            rows = _expand_rows(mat.indptr)
            slots = np.arange(mat.nnz, dtype=np.int64) - mat.indptr[rows]
            keep = slots < width
            cols[i, rows[keep], slots[keep]] = mat.indices[keep]
            vals[i, rows[keep], slots[keep]] = mat.data[keep]
            lens[i] = np.minimum(mat.row_nnz(), width)
        return PaddedCSR(to_device(cols), to_device(vals),
                         to_device(lens), (m_rows, n))
    padded = [m if isinstance(m, PaddedCSR) else padded_from_csr(m, width)
              for m in mats]
    return PaddedCSR(
        jnp.stack([p.cols for p in padded]),
        jnp.stack([p.vals for p in padded]),
        jnp.stack([p.lens for p in padded]),
        padded[0].shape)


def masked_spgemm_batched(As, B, Ms, *, algorithm: str = "auto",
                          semiring: Semiring = PLUS_TIMES,
                          complement: bool = False, plan=None):
    """Batch of C_i = M_i (.) (A_i B) with ONE plan and ONE compiled program.

    ``As``/``Ms``: equal-length sequences of same-shape operands (CSR or
    PaddedCSR); ``B`` is shared.  This is the multi-source traversal case
    (betweenness centrality): per-batch structures differ, but one plan —
    with pad widths widened to the batch maxima — serves every element, so
    the device sees a single vmapped program instead of len(As) dispatches.

    Returns a list of MaskedSpGEMMResult (mask case), or stacked dense
    ``(vals, present)`` of shape (batch, m, n) under ``complement``.
    """
    As, Ms = list(As), list(Ms)
    if len(As) != len(Ms) or not As:
        raise ValueError("As/Ms must be equal-length, non-empty")
    m, k = As[0].shape
    _, n = B.shape
    if plan is None and algorithm == "auto":
        from .planner import plan_batch
        plan = plan_batch(As, B, Ms, complement=complement,
                          semiring=semiring)
    if plan is not None and plan.algorithm == "tile":
        # a tile-elected plan (the serving engine hands these in) executes
        # each element on the block executors: the compiled executor is
        # shared across the batch (jit cache), the plan across every call
        from repro.kernels.masked_matmul.ops import tile_path_supported
        if not tile_path_supported(semiring.name, complement):
            raise NotImplementedError(
                "tile route requires plus_times and an explicit mask")
        return [_masked_spgemm_tile(a, B, mm,
                                    block_size=plan.tile_block or None,
                                    wm=plan.widths[2])
                for a, mm in zip(As, Ms)]
    if plan is not None:
        algorithm = plan.algorithm
        wa, wb, wm = plan.widths
    else:
        wa = max(1, max(int(np.diff(a.indptr).max(initial=0)) for a in As))
        wm = max(1, max(int(np.diff(mm.indptr).max(initial=0)) for mm in Ms))
        wb = None

    A_b = _stack_padded(As, wa)
    M_b = _stack_padded(Ms, wm)
    if algorithm == "inner":
        Bt = B.transpose() if isinstance(B, CSR) else B
        B_p = Bt if isinstance(Bt, PaddedCSR) else padded_from_csr(Bt, wb)
    else:
        B_p = B if isinstance(B, PaddedCSR) else padded_from_csr(B, wb)

    run = jax.vmap(lambda Mp, Ap: _masked_spgemm_padded(
        Mp, Ap, B_p, algorithm=algorithm, sr=semiring,
        complement=complement, n_inspect=None, shape=(m, n), kdim=k))
    vals, present = run(M_b, A_b)
    if complement:
        return vals, present
    return [MaskedSpGEMMResult(vals[i], present[i], M_b.cols[i], (m, n))
            for i in range(len(As))]


# ---------------------------------------------------------------------------
# Dense oracle (tests): structural semantics under a semiring
# ---------------------------------------------------------------------------


def dense_oracle(a, b, m, *, semiring: Semiring = PLUS_TIMES,
                 complement: bool = False):
    """Reference masked product on dense arrays.

    Returns (vals, present): present = structural nonzero AND mask allows;
    vals = semiring matmul where present (zero elsewhere).
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    m = jnp.asarray(m)
    structure = ((jnp.abs(a) > 0).astype(jnp.float32)
                 @ (jnp.abs(b) > 0).astype(jnp.float32)) > 0
    allowed = (m == 0) if complement else (m != 0)
    present = structure & allowed
    vals = semiring.matmul(a, b)
    return jnp.where(present, vals, semiring.zero), present
