"""Element-level Masked SpGEVM accumulators (paper Sec. 5), in JAX.

Each accumulator implements the paper's interface

    SETALLOWED(key) / INSERT(key, value) / REMOVE(key)

with the three states NOTALLOWED / ALLOWED / SET, specialized as a row-level
masked SpGEVM  v = m (.)  (u^T B)  over an arbitrary semiring.

Vectorization notes (faithfulness vs. the CPU paper):
  * The paper's scalar inner loop over a row of B is vectorized: one B-row is
    processed as a whole (the state transitions applied are identical because
    column ids within a CSR row are unique).
  * MCA/Heap use sorted-merge primitives.  ``searchsorted`` is the vectorized
    equivalent of the paper's sequential 2-way merge (same information flow,
    log-factor instead of linear scan); the Heap's multiway merge is realized
    as sort + segmented reduction, the standard data-parallel equivalent of a
    priority-queue merge.
  * Inner intersects its two index lists by comparing every pair of padded
    slots (``wa * wbt`` compares, no search, no element gather): on a TPU
    each step of a ``searchsorted`` and each gather after it is a scalar
    gather, far slower than a vector compare.
  * INSERT's lambda deferral ("only evaluate the product if it will not be
    discarded") becomes predication: products are computed vector-wide and
    masked, which on SIMD hardware is the same optimization.

All functions operate on a single row and are ``vmap``-ed by the driver in
``masked_spgemm.py``.  Static widths: pm = mask-row pad, wa = A-row pad,
wb = B-row pad.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from .semiring import Semiring

NOTALLOWED, ALLOWED, SET = 0, 1, 2


def _b_row(B_cols, B_vals, B_lens, row, kdim):
    """Fetch one padded row of B, masking padding and out-of-range rows."""
    safe = jnp.minimum(row, kdim - 1)
    cols = B_cols[safe]
    vals = B_vals[safe]
    valid = (jnp.arange(cols.shape[0]) < B_lens[safe]) & (row < kdim)
    return cols, vals, valid


# ---------------------------------------------------------------------------
# MSA: dense values[n] + states[n]  (paper Sec. 5.2)
# ---------------------------------------------------------------------------


def msa_row(m_cols, a_cols, a_vals, a_len, B_cols, B_vals, B_lens,
            n: int, kdim: int, sr: Semiring, complement: bool = False):
    """Masked SpGEVM with the Masked Sparse Accumulator.

    Returns (vals, present) aligned to mask slots when ``complement=False``;
    dense (n,) row otherwise (complemented output is not mask-aligned).
    """
    values = jnp.full((n + 1,), sr.zero, dtype=B_vals.dtype)
    if complement:
        states = jnp.full((n + 1,), ALLOWED, dtype=jnp.int8)
        states = states.at[m_cols].set(NOTALLOWED)  # SETNOTALLOWED
        states = states.at[n].set(NOTALLOWED)       # scratch slot
    else:
        states = jnp.full((n + 1,), NOTALLOWED, dtype=jnp.int8)
        states = states.at[m_cols].set(ALLOWED)     # SETALLOWED; pads hit slot n
        states = states.at[n].set(NOTALLOWED)

    def insert_row(k, carry):
        values, states = carry
        uk = a_vals[k]
        bcols, bvals, bvalid = _b_row(B_cols, B_vals, B_lens, a_cols[k], kdim)
        bvalid = bvalid & (k < a_len)
        st = states[bcols]
        allowed = (st >= ALLOWED) & bvalid
        prod = sr.mul(uk, bvals)                      # predicated lambda
        new = jnp.where(allowed, sr.add(values[bcols], prod), values[bcols])
        values = values.at[bcols].set(new)            # cols unique within row
        states = states.at[bcols].set(jnp.where(allowed, SET, st).astype(jnp.int8))
        return values, states

    values, states = jax.lax.fori_loop(0, a_cols.shape[0], insert_row,
                                       (values, states))
    if complement:
        present = states[:n] == SET
        return jnp.where(present, values[:n], sr.zero), present
    # gather in mask order (REMOVE per mask nonzero) -> stable output
    out = values[m_cols]
    present = (states[m_cols] == SET) & (m_cols < n)
    return jnp.where(present, out, sr.zero), present


# ---------------------------------------------------------------------------
# Hash: open addressing, linear probing, load factor 0.25 (paper Sec. 5.3)
# ---------------------------------------------------------------------------


def _hash_size(pm: int, load: float = 0.25) -> int:
    t = 1
    need = max(4, int(pm / load))
    while t < need:
        t <<= 1
    return t


def _probe(keys, queries, table_size):
    """Vectorized linear probing: slot of each query (or slot of first EMPTY).

    Returns (slots, found).  EMPTY = -1.
    """
    h = (queries.astype(jnp.uint32) * jnp.uint32(2654435761)) & jnp.uint32(table_size - 1)
    slots = h.astype(jnp.int32)

    def cond(c):
        _, done = c
        return ~jnp.all(done)

    def body(c):
        slots, done = c
        at = keys[slots]
        hit = (at == queries) | (at == -1)
        new_done = done | hit
        slots = jnp.where(new_done, slots, (slots + 1) & (table_size - 1))
        return slots, new_done

    slots, _ = jax.lax.while_loop(
        cond, body, (slots, jnp.zeros_like(queries, dtype=bool)))
    found = keys[slots] == queries
    return slots, found


def hash_row(m_cols, a_cols, a_vals, a_len, B_cols, B_vals, B_lens,
             n: int, kdim: int, sr: Semiring, table_size: int = 0):
    """Masked SpGEVM with the hash accumulator (non-complemented mask)."""
    pm = m_cols.shape[0]
    T = table_size or _hash_size(pm)
    keys = jnp.full((T,), -1, dtype=jnp.int32)
    values = jnp.full((T,), sr.zero, dtype=B_vals.dtype)
    states = jnp.full((T,), NOTALLOWED, dtype=jnp.int8)

    # SETALLOWED for every mask nonzero (sequential inserts, like the paper)
    def set_allowed(i, carry):
        keys, states = carry
        c = m_cols[i]
        valid = c < n
        slots, _ = _probe(keys, jnp.array([c], jnp.int32), T)
        s = slots[0]
        keys = jnp.where(valid, keys.at[s].set(c), keys)
        states = jnp.where(valid, states.at[s].set(ALLOWED), states)
        return keys, states

    keys, states = jax.lax.fori_loop(0, pm, set_allowed, (keys, states))

    def insert_row(k, carry):
        values, states = carry
        uk = a_vals[k]
        bcols, bvals, bvalid = _b_row(B_cols, B_vals, B_lens, a_cols[k], kdim)
        bvalid = bvalid & (k < a_len)
        slots, found = _probe(keys, bcols.astype(jnp.int32), T)
        allowed = found & bvalid & (states[slots] >= ALLOWED)
        prod = sr.mul(uk, bvals)
        new = jnp.where(allowed, sr.add(values[slots], prod), values[slots])
        values = values.at[slots].set(new)
        states = states.at[slots].set(
            jnp.where(allowed, SET, states[slots]).astype(jnp.int8))
        return values, states

    values, states = jax.lax.fori_loop(0, a_cols.shape[0], insert_row,
                                       (values, states))
    # REMOVE in mask order
    slots, found = _probe(keys, m_cols.astype(jnp.int32), T)
    present = found & (states[slots] == SET) & (m_cols < n)
    return jnp.where(present, values[slots], sr.zero), present


# ---------------------------------------------------------------------------
# MCA: compressed accumulator indexed by mask rank (paper Sec. 5.4; novel)
# ---------------------------------------------------------------------------


def mca_row(m_cols, a_cols, a_vals, a_len, B_cols, B_vals, B_lens,
            n: int, kdim: int, sr: Semiring):
    """Masked SpGEVM with the Mask Compressed Accumulator.

    Accumulator arrays have length nnz(m) (= pm padded); keys are the *ranks*
    of mask nonzeros.  Only ALLOWED/SET states exist.  No complement support
    (faithful to the paper).  ``searchsorted`` plays the role of the sorted
    mask/B-row merge.
    """
    pm = m_cols.shape[0]
    # one scratch slot at index pm absorbs every non-hit scatter: a clamped
    # miss must never alias a hit slot (duplicate-index .at[].set order is
    # unspecified and would otherwise drop accumulations)
    values = jnp.full((pm + 1,), sr.zero, dtype=B_vals.dtype)
    states = jnp.zeros((pm + 1,), dtype=jnp.int8)  # 0 = ALLOWED, 1 = SET

    def insert_row(k, carry):
        values, states = carry
        uk = a_vals[k]
        bcols, bvals, bvalid = _b_row(B_cols, B_vals, B_lens, a_cols[k], kdim)
        bvalid = bvalid & (k < a_len)
        idx = jnp.searchsorted(m_cols, bcols).astype(jnp.int32)
        idxc = jnp.minimum(idx, pm - 1)
        hit = (m_cols[idxc] == bcols) & (bcols < n) & bvalid & (idx < pm)
        tgt = jnp.where(hit, idxc, pm)
        prod = sr.mul(uk, bvals)
        new = jnp.where(hit, sr.add(values[idxc], prod), sr.zero)
        values = values.at[tgt].set(new)
        states = states.at[tgt].set(jnp.where(hit, 1, 0).astype(jnp.int8))
        return values, states

    values, states = jax.lax.fori_loop(0, a_cols.shape[0], insert_row,
                                       (values, states))
    present = (states[:pm] == 1) & (m_cols < n)
    return jnp.where(present, values[:pm], sr.zero), present


# ---------------------------------------------------------------------------
# Heap: multiway merge of scaled B-rows (paper Sec. 5.5)
# ---------------------------------------------------------------------------


def _segmented_reduce_sorted(cols, vals, sr: Semiring, n: int):
    """Combine values of equal, sorted cols: returns (cols, vals, is_tail).

    ``is_tail[i]`` marks the last element of each equal-col run; vals at the
    tail hold the run's semiring-sum (matches the paper's "accumulate into
    the last inserted output entry" logic, Alg. 4 lines 14-18).
    """
    newseg = jnp.concatenate([jnp.ones((1,), bool), cols[1:] != cols[:-1]])

    def combine(a, b):
        (va, sa), (vb, sb) = a, b
        v = jnp.where(sb, vb, sr.add(va, vb))
        return v, sa | sb  # segment flag must OR both sides (associativity)

    vals_scan, _ = jax.lax.associative_scan(combine, (vals, newseg))
    is_tail = jnp.concatenate([cols[1:] != cols[:-1], jnp.ones((1,), bool)])
    is_tail = is_tail & (cols < n)
    return cols, vals_scan, is_tail


def heap_row(m_cols, a_cols, a_vals, a_len, B_cols, B_vals, B_lens,
             n: int, kdim: int, sr: Semiring, n_inspect: int = 1,
             complement: bool = False):
    """Masked SpGEVM via multiway merge (Heap / HeapDot).

    ``n_inspect`` mirrors the paper's NInspect: 0 pushes every element and
    filters against the mask during the merge (Heap); >=1 ("HeapDot" when
    inf) checks mask membership *before* an element enters the merge.  The
    data-parallel merge is sort + segmented semiring-reduction.
    """
    wa, wb = a_cols.shape[0], B_cols.shape[1]
    pm = m_cols.shape[0]

    def one_source(k):
        uk = a_vals[k]
        bcols, bvals, bvalid = _b_row(B_cols, B_vals, B_lens, a_cols[k], kdim)
        bvalid = bvalid & (k < a_len)
        prod = sr.mul(uk, bvals)
        if n_inspect > 0 and not complement:
            idx = jnp.minimum(jnp.searchsorted(m_cols, bcols), pm - 1)
            in_mask = (m_cols[idx] == bcols)
            bvalid = bvalid & in_mask  # inspect mask before pushing
        cols = jnp.where(bvalid, bcols, n)
        return cols, jnp.where(bvalid, prod, sr.zero)

    cols, vals = jax.vmap(one_source)(jnp.arange(wa))
    cols, vals = cols.reshape(-1), vals.reshape(-1)
    order = jnp.argsort(cols)                     # == heap-ordered extraction
    cols, vals = cols[order], vals[order]
    cols, vals, is_tail = _segmented_reduce_sorted(cols, vals, sr, n)

    if complement:
        # products for S \ m: drop merged entries whose col is in the mask
        idx = jnp.minimum(jnp.searchsorted(m_cols, cols), pm - 1)
        in_mask = (m_cols[idx] == cols)
        keep = is_tail & ~in_mask
        dense = jnp.full((n + 1,), sr.zero, dtype=vals.dtype)
        densep = jnp.zeros((n + 1,), bool)
        dense = dense.at[jnp.where(keep, cols, n)].set(vals)
        densep = densep.at[jnp.where(keep, cols, n)].set(True)
        return dense[:n], densep[:n]

    # align merged run-tails to mask slots (scatter only the hits; a slot is
    # hit by at most one run tail since mask cols are unique)
    out = jnp.full((pm + 1,), sr.zero, dtype=vals.dtype)
    present = jnp.zeros((pm + 1,), bool)
    idx = jnp.searchsorted(m_cols, cols).astype(jnp.int32)
    idxc = jnp.minimum(idx, pm - 1)
    hit = (m_cols[idxc] == cols) & is_tail
    tgt = jnp.where(hit, idxc, pm)
    out = out.at[tgt].set(vals)
    present = present.at[tgt].set(hit)
    return out[:pm], present[:pm] & (m_cols < n)


# ---------------------------------------------------------------------------
# Inner: pull-based dot products per mask nonzero (paper Sec. 4.1)
# ---------------------------------------------------------------------------


def _intersect(a_cols, a_ok, bcols, bbits):
    """Each A slot's matching B value, as its bits, and whether it matched,
    by comparing every (A slot, B slot) pair: ``wa * wbt`` vector compares,
    no search and no element gather.  Column ids within a row are unique,
    so an A slot matches at most one B slot, and OR-ing the bits over the
    B slots returns the matched value's bit for bit.  ``bcols`` holds an
    out-of-range id in every invalid slot, which no valid A slot equals."""
    hit = (a_cols[:, None] == bcols[None, :]) & a_ok[:, None]
    bits = jnp.where(hit, bbits[None, :], jnp.zeros((), bbits.dtype))
    bits = jax.lax.reduce(bits, jnp.zeros((), bbits.dtype), jax.lax.bitwise_or,
                          (1,))
    return bits, jnp.any(hit, 1)


def inner_row(m_cols, a_cols, a_vals, a_len,
              Bt_cols, Bt_vals, Bt_lens, n: int, kdim: int, sr: Semiring):
    """Pull algorithm: for each mask nonzero j, sparse dot  A_i* . B_*j.

    ``Bt_*`` is B stored column-major (CSC == CSR of B^T), as the paper
    prescribes.  The two sorted index lists are intersected by an equality
    compare over their padded slots (``_intersect``).
    """
    wa = a_cols.shape[0]
    a_ok = (jnp.arange(wa) < a_len) & (a_cols < kdim)
    # B^T's columns with kdim in every slot past the row's length, and its
    # value bits, built once (neither depends on the row): a mask slot then
    # gathers two whole rows and no scalar length
    bt_cols = jnp.where(jnp.arange(Bt_cols.shape[1]) < Bt_lens[:, None],
                        Bt_cols, kdim)
    bits_t = jnp.dtype(f"uint{8 * Bt_vals.dtype.itemsize}")
    bt_bits = jax.lax.bitcast_convert_type(Bt_vals, bits_t)

    def one_dot(j):
        bits, hit = _intersect(a_cols, a_ok, bt_cols[j], bt_bits[j])
        matched = jax.lax.bitcast_convert_type(bits, Bt_vals.dtype)
        contrib = jnp.where(hit, sr.mul(a_vals, matched), sr.zero)
        # semiring-reduce the intersection
        red = jax.lax.reduce(contrib, jnp.asarray(sr.zero, contrib.dtype),
                             sr.add, (0,))
        return red, jnp.any(hit)

    vals, present = jax.vmap(one_dot)(jnp.minimum(m_cols, n - 1))
    present = present & (m_cols < n)
    return jnp.where(present, vals, sr.zero), present


# ---------------------------------------------------------------------------
# Symbolic (counting-only) variants for the two-phase pipeline (paper Sec. 6)
# ---------------------------------------------------------------------------


def symbolic_row(m_cols, a_cols, a_len, B_cols, B_lens, n: int, kdim: int):
    """Number of output nonzeros of one masked row (structure only).

    Mirrors MCA with boolean states and no value computation -- the cheapest
    faithful symbolic pass.
    """
    pm = m_cols.shape[0]
    states = jnp.zeros((pm + 1,), bool)  # scratch slot pm absorbs misses

    def body(k, states):
        bcols = B_cols[jnp.minimum(a_cols[k], kdim - 1)]
        bvalid = (jnp.arange(bcols.shape[0]) <
                  B_lens[jnp.minimum(a_cols[k], kdim - 1)])
        bvalid = bvalid & (a_cols[k] < kdim) & (k < a_len)
        idx = jnp.minimum(jnp.searchsorted(m_cols, bcols), pm - 1)
        hit = (m_cols[idx] == bcols) & (bcols < n) & bvalid
        return states.at[jnp.where(hit, idx, pm)].set(True)

    states = jax.lax.fori_loop(0, a_cols.shape[0], body, states)
    return jnp.sum((states[:pm] & (m_cols < n)).astype(jnp.int32))


# ---------------------------------------------------------------------------
# Cost hooks (planner): per-algorithm work models over padded row widths
# ---------------------------------------------------------------------------
#
# The planner (``planner.py``) chooses among the accumulators by evaluating
# these models on cheap structural statistics.  The models describe THIS
# vectorized implementation, not the paper's scalar CPU loops: every row is
# padded to the static widths wa/wb/pm, so padded products (not true flops)
# are what the hardware executes.  Units: estimated milliseconds per 1024
# output rows on the calibration host; only the *ranking* matters, and the
# constants are tunable (see ROADMAP "Open items" for the re-calibration
# procedure against BENCH_density / the rmat suite).

#: Calibration constants — SHIPPED CPU defaults, fit to
#: benchmarks/bench_density.py (n=1024 ER grid) plus skewed R-MAT and
#: dense-mask probes.  On other backends don't hand-edit: ``python -m
#: repro.tune`` measures the kernels and refits these (and TILE_COST /
#: DIST_COST / the tile gates) into a CalibrationProfile, and
#: ``repro.tuning.activate`` installs it here in place.  The planner keys
#: its plan caches on a fingerprint of these tables, so any change —
#: activation or manual mutation — invalidates previously cached plans.
COST_CONSTANTS = {
    # dense (n+1)-wide state init/gather + wa sequential scatter rounds
    "msa": dict(base=12.0, per_n=0.035, per_flop=0.25, per_mask=0.5),
    # table build is a sequential probe loop over mask nonzeros; probing
    # inside the flop loop is a while-loop per batch of wb queries
    "hash": dict(base=40.0, per_flop=0.30, per_mask=1.5, per_slot=0.01),
    # wa merge rounds of wb searchsorted lookups into the pm-long mask row
    "mca": dict(base=45.0, per_merge=0.045),
    # sort of the wa*wb expansion + segmented reduce + mask alignment
    "heap": dict(base=25.0, per_sort=0.05, per_mask=1.0),
    "heapdot": dict(base=25.0, per_sort=0.05, per_mask=1.0, per_inspect=0.01),
    # one vmapped sparse dot per mask nonzero (no sequential flop loop);
    # the large base is the host-side B^T transpose+pad paid every call
    "inner": dict(base=51.0, per_dot=0.0157),
}


def _log2(x: float) -> float:
    import math
    return math.log2(max(2.0, float(x)))


# Each model is LINEAR in its constants: cost = sum_k c[k] * feature_k.
# The feature functions below are that decomposition, shared between the
# hooks (dot with COST_CONSTANTS) and the calibration fit in
# ``repro.tuning.fit`` (least squares over the same features) — one
# functional form, two readers, no way to drift apart.


def _msa_features(*, n, wa, wb, wbt, pm):
    # dense (n+1)-wide state init/gather + wa sequential scatter rounds
    return {"base": 1.0, "per_n": float(n + 1), "per_flop": float(wa * wb),
            "per_mask": float(pm)}


def _hash_features(*, n, wa, wb, wbt, pm):
    # table build is a sequential probe loop over mask nonzeros; probing
    # inside the flop loop is a while-loop per batch of wb queries
    return {"base": 1.0, "per_flop": float(wa * wb), "per_mask": float(pm),
            "per_slot": float(_hash_size(max(1, pm)))}


def _mca_features(*, n, wa, wb, wbt, pm):
    # wa merge rounds of wb searchsorted lookups into the pm-long mask row
    return {"base": 1.0, "per_merge": wa * wb * _log2(pm + 2)}


def _heap_features(*, n, wa, wb, wbt, pm):
    # sort of the wa*wb expansion + segmented reduce + mask alignment
    e = wa * wb
    return {"base": 1.0, "per_sort": e * _log2(e + 2), "per_mask": float(pm)}


def _heapdot_features(*, n, wa, wb, wbt, pm):
    e = wa * wb
    return {"base": 1.0, "per_sort": e * _log2(e + 2), "per_mask": float(pm),
            "per_inspect": e * _log2(pm + 2)}


def _inner_features(*, n, wa, wb, wbt, pm):
    # one vmapped sparse dot per mask nonzero (no sequential flop loop);
    # the base is the host-side B^T transpose+pad paid every call
    return {"base": 1.0, "per_dot": pm * wa * _log2(wbt + 2)}


#: algorithm name -> feature decomposition of its cost model
COST_FEATURES = {
    "msa": _msa_features,
    "hash": _hash_features,
    "mca": _mca_features,
    "heap": _heap_features,
    "heapdot": _heapdot_features,
    "inner": _inner_features,
}


def _make_cost_hook(name):
    features = COST_FEATURES[name]

    def hook(*, n, wa, wb, wbt, pm):
        c = COST_CONSTANTS[name]
        f = features(n=n, wa=wa, wb=wb, wbt=wbt, pm=pm)
        return sum(c[k] * f[k] for k in f)

    hook.__name__ = f"{name}_cost"
    return hook


#: algorithm name -> cost hook; keys mirror masked_spgemm.ALGORITHMS
COST_HOOKS = {name: _make_cost_hook(name) for name in COST_FEATURES}

# named aliases, kept for direct callers
msa_cost = COST_HOOKS["msa"]
hash_cost = COST_HOOKS["hash"]
mca_cost = COST_HOOKS["mca"]
heap_cost = COST_HOOKS["heap"]
heapdot_cost = COST_HOOKS["heapdot"]
inner_cost = COST_HOOKS["inner"]

# ---------------------------------------------------------------------------
# Device workspace (planner): what a row kernel keeps live per vmapped row
# ---------------------------------------------------------------------------
#
# The driver vmaps a row kernel over every row at once, so its per-row state
# is multiplied by m.  These count bytes per row: the carried accumulator
# state plus the widest intermediate of one step (a gathered B row is 9
# bytes per slot: int32 col, f32 value, bool validity).  A kernel whose
# count exceeds the device cannot run there; the planner drops it
# (``planner.rank_algorithms``).  On a 16 GB v5e at R-MAT scale 16 that
# removes msa (dense (n+1)-wide f32+int8 state: 21 GB), heap/heapdot (the
# wa*wb expansion: 32 GB) and inner (a wbt-wide B^T row per mask slot: the
# chip's compiler aborts on its 32-bit bounds long before memory runs out).
# At scale 13 inner's count, 17.0 GB, is what the chip's compiler reports
# for its program, and the v5e's limit is 16.9 GB.


def _msa_workspace(*, n, wa, wb, wbt, pm):
    return 5.0 * (n + 1) + 9.0 * wb


def _hash_workspace(*, n, wa, wb, wbt, pm):
    return 9.0 * _hash_size(max(1, pm)) + 9.0 * wb


def _mca_workspace(*, n, wa, wb, wbt, pm):
    return 5.0 * (pm + 1) + 9.0 * wb


def _heap_workspace(*, n, wa, wb, wbt, pm):
    return 8.0 * wa * wb


def _inner_workspace(*, n, wa, wb, wbt, pm):
    # per mask slot: the gathered B^T row, held as columns, value bits and
    # one relayout copy (12 bytes a slot; the compare fuses into its reduce)
    return pm * (13.0 * wbt + 4.0 * wa)


#: algorithm name -> device bytes per vmapped row
ROW_WORKSPACE = {
    "msa": _msa_workspace,
    "hash": _hash_workspace,
    "mca": _mca_workspace,
    "heap": _heap_workspace,
    "heapdot": _heap_workspace,
    "inner": _inner_workspace,
}

#: algorithms whose row kernels accept ``complement=True`` (paper Sec. 8.4:
#: hash/MCA/inner require an explicit mask)
SUPPORTS_COMPLEMENT = frozenset({"msa", "heap", "heapdot"})
