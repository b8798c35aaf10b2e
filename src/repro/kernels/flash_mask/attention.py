"""Attention as Masked SpGEMM.

``scores = M (.) (Q Kᵀ)`` is a masked matrix product with a *structured*
mask (causal / sliding-window / dense-prefix).  Three implementations:

* ``dense_masked`` — the paper's Fig.-1 strawman: compute ALL scores, then
  mask.  Quadratic flops regardless of mask.  Baseline for §Perf.
* ``block_masked`` — the paper's pull algorithm at MXU-tile granularity,
  expressed in XLA: a host-built tile worklist (only mask-admitted tiles),
  load-balanced by pairing long rows with short rows (folded-causal), then
  executed as a scan of uniform gather+matmul+streaming-softmax chunks.
  The HLO flop count shows the saving (≈2x for causal, S/W for windows).
* Pallas runtime kernel (``ops.flash_mask_attention``) — same worklist,
  VMEM streaming, for real TPU execution.

``decode_attention`` is the single-token path over a (possibly
ring-buffered) KV cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import caches

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parametric mask (``ref.mask_allowed`` has no bidirectional prefix)
# ---------------------------------------------------------------------------


def allowed_fn(qpos, kpos, *, causal: bool, window: int, prefix: int):
    ok = jnp.ones(jnp.broadcast_shapes(qpos.shape, kpos.shape), jnp.bool_)
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= ((qpos - kpos) < window) | (kpos < prefix)
    if prefix > 0 and window == 0:
        # prefix-LM: bidirectional within the prefix
        ok |= (kpos < prefix) & (qpos < prefix)
    return ok


# ---------------------------------------------------------------------------
# dense baseline (plain product + mask)
# ---------------------------------------------------------------------------


def dense_masked_attention(q, k, v, *, causal=True, window=0, prefix=0,
                           q_offset=0, scale=None):
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D).  Full quadratic scores."""
    b, hq, s_q, d = q.shape
    _, hkv, s_k, _ = k.shape
    g = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    qg = q.reshape(b, hkv, g, s_q, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(s_q)[:, None] + q_offset
    kpos = jnp.arange(s_k)[None, :]
    ok = allowed_fn(qpos, kpos, causal=causal, window=window, prefix=prefix)
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, s_q, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# block-masked (paper pull algorithm, balanced worklist, XLA)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=caches.env_capacity("REPRO_ATTN_SCHED_CAP",
                                                 256))
def _balanced_schedule(s_q: int, s_k: int, bq: int, bk: int, causal: bool,
                       window: int, prefix: int, q_offset: int,
                       chunk: int = 8):
    """Host symbolic phase: per-q-block tile lists, folded into G groups of
    2 rows with near-equal total work, padded to a common chunked length.

    Returns numpy arrays:
      q_ids  (G, 2)  row ids of the two members
      kv_ids (G, E)  gathered kv block per entry (pad: 0)
      member (G, E)  0/1 member index per entry
      valid  (G, E)  entry is real
    """
    nq, nk = s_q // bq, s_k // bk
    i = np.arange(nq)[:, None]
    j = np.arange(nk)[None, :]
    q_lo, q_hi = i * bq + q_offset, (i + 1) * bq - 1 + q_offset
    k_lo, k_hi = j * bk, (j + 1) * bk - 1
    ok = np.ones((nq, nk), bool)
    if causal:
        ok &= k_lo <= q_hi
    if window > 0:
        in_win = (q_lo - k_hi) < window
        if causal:
            in_win &= (q_hi - k_lo) >= 0
        else:
            in_win &= (k_lo - q_hi) < window
        ok &= in_win | np.broadcast_to(k_lo < prefix, in_win.shape)
    if prefix > 0 and window == 0:
        ok |= (k_lo < prefix) & (q_lo < prefix).reshape(-1, 1)
    ok[~ok.any(axis=1), 0] = True

    lists = [np.nonzero(ok[r])[0] for r in range(nq)]
    order = np.argsort([-len(l) for l in lists], kind="stable")
    if nq % 2:                      # odd: last group has one member
        order = np.concatenate([order, [order[-1]]])
    half = len(order) // 2
    groups = [(order[t], order[len(order) - 1 - t]) for t in range(half)]

    raw_e = max(len(lists[a]) + (len(lists[b]) if b != a else 0)
                for a, b in groups)
    steps = max(1, -(-raw_e // chunk))
    E = steps * (-(-raw_e // steps))
    G = len(groups)
    q_ids = np.zeros((G, 2), np.int32)
    scatter_ids = np.full((G, 2), nq, np.int32)   # nq == dropped write
    kv_ids = np.zeros((G, E), np.int32)
    member = np.zeros((G, E), np.int32)
    valid = np.zeros((G, E), bool)
    seen = set()
    for g, (a, b) in enumerate(groups):
        q_ids[g] = (a, b)
        for slot, row in ((0, int(a)), (1, int(b))):
            if row not in seen:        # duplicated rows write exactly once
                seen.add(row)
                scatter_ids[g, slot] = row
        ents = [(0, int(x)) for x in lists[a]]
        if b != a:
            ents += [(1, int(x)) for x in lists[b]]
        for e, (m, kvb) in enumerate(ents):
            member[g, e] = m
            kv_ids[g, e] = kvb
            valid[g, e] = True
    return q_ids, scatter_ids, kv_ids, member, valid, E // steps


caches.register_lru("attention-block-schedule", _balanced_schedule)


def block_masked_attention(q, k, v, *, causal=True, window=0, prefix=0,
                           q_offset=0, scale=None, bq=128, bk=128):
    """Pull-based masked attention: only mask-admitted tiles are computed.

    q: (B, Hq, S, D); k, v: (B, Hkv, T, D).  Returns (B, Hq, S, D).
    """
    b, hq, s_q, d = q.shape
    _, hkv, s_k, _ = k.shape
    g_rep = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    bq_, bk_ = min(bq, s_q), min(bk, s_k)
    if s_q % bq_ or s_k % bk_:
        return dense_masked_attention(q, k, v, causal=causal, window=window,
                                      prefix=prefix, q_offset=q_offset,
                                      scale=scale)
    if not causal and window == 0:
        # mask fully dense -> the plain product IS the masked product
        return dense_masked_attention(q, k, v, causal=False, window=0,
                                      prefix=0, q_offset=q_offset,
                                      scale=scale)

    q_ids, scatter_ids, kv_ids, member, valid, chunk = _balanced_schedule(
        s_q, s_k, bq_, bk_, causal, window, prefix, q_offset)
    G, E = kv_ids.shape
    steps = E // chunk
    q_ids_j = jnp.asarray(q_ids)
    scatter_j = jnp.asarray(scatter_ids)
    kv_c = jnp.asarray(kv_ids.reshape(G, steps, chunk))
    mem_c = jnp.asarray(member.reshape(G, steps, chunk))
    val_c = jnp.asarray(valid.reshape(G, steps, chunk))

    dv = v.shape[-1]

    def one_head(qh, kh, vh):
        # qh: (S, Dqk) one query head; kh: (T, Dqk); vh: (T, Dv)
        qb = qh.reshape(s_q // bq_, bq_, d)
        kb = kh.reshape(s_k // bk_, bk_, d)
        vb = vh.reshape(s_k // bk_, bk_, dv)

        def one_group(qid2, kv_s, mem_s, val_s):
            qg = qb[qid2]                        # (2, bq, d)

            def step(carry, xs):
                m_run, l_run, acc = carry        # (2,bq),(2,bq),(2,bq,d)
                kv_e, mem_e, val_e = xs          # (chunk,) each
                ke = kb[kv_e]                    # (c, bk, d)
                ve = vb[kv_e]
                qe = qg[mem_e]                   # (c, bq, d)
                # native-dtype operands + f32 accumulation: bf16 inputs
                # must NOT be copied up to f32 (2x HBM traffic, §Perf A2)
                s = jnp.einsum("cqd,ckd->cqk", qe, ke,
                               preferred_element_type=jnp.float32) * scale
                qrow = qid2[mem_e]               # (c,)
                qp = (qrow[:, None] * bq_ + jnp.arange(bq_)[None, :]
                      + q_offset)                # (c, bq)
                kp = kv_e[:, None] * bk_ + jnp.arange(bk_)[None, :]  # (c, bk)
                ok = allowed_fn(qp[:, :, None], kp[:, None, :],
                                causal=causal, window=window, prefix=prefix)
                ok &= val_e[:, None, None]
                s = jnp.where(ok, s, NEG_INF)
                # per-entry partials
                m_e = jnp.max(s, axis=-1)                    # (c, bq)
                p = jnp.where(ok, jnp.exp(s - m_e[..., None]), 0.0)
                l_e = jnp.sum(p, axis=-1)                    # (c, bq)
                p_mm = p.astype(jnp.promote_types(ve.dtype, jnp.bfloat16))
                o_e = jnp.einsum("cqk,ckd->cqd", p_mm, ve,
                                 preferred_element_type=jnp.float32)
                # combine the chunk's entries into the 2 members
                sel = jax.nn.one_hot(mem_e, 2, dtype=jnp.float32)  # (c, 2)
                m_e = jnp.where(l_e > 0, m_e, NEG_INF)
                m_grp = jnp.max(
                    jnp.where(sel.T[:, :, None] > 0, m_e[None], NEG_INF),
                    axis=1)                                   # (2, bq)
                m_new = jnp.maximum(m_run, m_grp)
                w_e = jnp.exp(m_e - m_new[mem_e]) * (l_e > 0)  # (c, bq)
                l_add = jnp.einsum("cm,cq->mq", sel, w_e * l_e)
                o_add = jnp.einsum("cm,cqd->mqd", sel,
                                   w_e[..., None] * o_e)
                alpha = jnp.exp(m_run - m_new)
                l_new = l_run * alpha + l_add
                acc_new = acc * alpha[..., None] + o_add
                return (m_new, l_new, acc_new), None

            init = (jnp.full((2, bq_), NEG_INF, jnp.float32),
                    jnp.zeros((2, bq_), jnp.float32),
                    jnp.zeros((2, bq_, dv), jnp.float32))
            (m_run, l_run, acc), _ = jax.lax.scan(
                step, init, (kv_s, mem_s, val_s))
            # where-guarded denominator: with maximum(l, tiny), backward
            # computes 1/l^2 = inf (f32 overflow) and 0*inf = NaN for
            # fully-masked members
            l_safe = jnp.where(l_run > 0, l_run, 1.0)[..., None]
            return jnp.where(l_run[..., None] > 0, acc / l_safe, 0.0)

        out_g = jax.vmap(one_group)(q_ids_j, kv_c, mem_c, val_c)
        # scatter rows back; duplicate members carry a drop sentinel
        out = jnp.zeros((s_q // bq_, bq_, dv), jnp.float32)
        out = out.at[scatter_j.reshape(-1)].set(
            out_g.reshape(-1, bq_, dv), mode="drop")
        return out.reshape(s_q, dv)

    qg = q.reshape(b, hkv, g_rep, s_q, d)
    f = jax.vmap(jax.vmap(jax.vmap(one_head, in_axes=(0, None, None)),
                          in_axes=(0, 0, 0)), in_axes=(0, 0, 0))
    out = f(qg, k, v)
    return out.reshape(b, hq, s_q, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# dispatcher + decode
# ---------------------------------------------------------------------------


def attention(q, k, v, *, impl="block_masked", causal=True, window=0,
              prefix=0, q_offset=0, scale=None, block=128):
    if impl == "dense_masked":
        return dense_masked_attention(q, k, v, causal=causal, window=window,
                                      prefix=prefix, q_offset=q_offset,
                                      scale=scale)
    if impl == "block_masked":
        return block_masked_attention(q, k, v, causal=causal, window=window,
                                      prefix=prefix, q_offset=q_offset,
                                      scale=scale, bq=block, bk=block)
    if impl == "flash_pallas":
        from .ops import flash_mask_attention
        return flash_mask_attention(q, k, v, causal=causal, window=window,
                                    prefix=prefix, q_offset=q_offset,
                                    scale=scale, bq=block, bk=block)
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(q, k_cache, v_cache, cache_len, *, window=0, prefix=0,
                     scale=None):
    """One-token decode. q: (B, Hq, D); caches: (B, Hkv, T, D).

    ``cache_len``: (B,) int32 — valid prefix length (query position is
    cache_len - 1 after the cache insert).  Ring-buffered caches pass the
    physical layout; masking is by validity only.
    """
    b, hq, d = q.shape
    _, hkv, t, _ = k_cache.shape
    g = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    qg = q.reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bhtd->bhgt", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(t)[None, :]
    ok = pos < cache_len[:, None]                      # (B, T)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgt,bhtd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, v_cache.shape[-1]).astype(q.dtype)
