"""Per-layer building blocks: GQA attention, MLA, MLP, MoE.

Every block is an (init, apply) pair over plain dicts so layers can be
stacked with ``jax.vmap(init)`` and scanned with ``jax.lax.scan`` (compile
time independent of depth).  Decode variants take/update caches.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, MoECfg, MLACfg
from .attention import attention, decode_attention
from .common import dense_init, rms_norm, layer_norm, rope, shard, DP, TP


def _norm(cfg: ModelConfig, params, x, name):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, params[f"{name}_scale"])
    return layer_norm(x, params[f"{name}_scale"], params[f"{name}_bias"])


def init_norm(cfg: ModelConfig, name):
    p = {f"{name}_scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.norm == "layernorm":
        p[f"{name}_bias"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# GQA attention (RoPE), train + decode
# ---------------------------------------------------------------------------


def _kv_names(cfg: ModelConfig):
    # name-swap selects the sharding rule (common._RULES is path-keyed)
    return ("wk_rep", "wv_rep") if cfg.kv_replicated else ("wk", "wv")


def init_attn(key, cfg: ModelConfig):
    hd = cfg.hd
    nk, nv = _kv_names(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.n_heads * hd)),
        nk: dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads * hd)),
        nv: dense_init(ks[2], (cfg.d_model, cfg.n_kv_heads * hd)),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, cfg.d_model)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
    return p


def _qkv(params, cfg: ModelConfig, x, positions):
    b, s, _ = x.shape
    hd = cfg.hd
    nk, nv = _kv_names(cfg)
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params[nk].astype(x.dtype)
    v = x @ params[nv].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = rope(q, positions[:, None, :], cfg.rope_theta)
    k = rope(k, positions[:, None, :], cfg.rope_theta)
    q = shard(q, DP, TP, None, None)
    kv_tp = None if cfg.kv_replicated else TP
    k = shard(k, DP, kv_tp, None, None)
    v = shard(v, DP, kv_tp, None, None)
    return q, k, v


def apply_attn(params, cfg: ModelConfig, x, positions, *, causal=True,
               prefix=0, q_offset=0, window=None):
    """x: (B, S, D) -> (B, S, D)."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, cfg, x, positions)
    window = cfg.window if window is None else window
    out = attention(q, k, v, impl=cfg.attn_impl, causal=causal,
                    window=window, prefix=prefix, q_offset=q_offset,
                    block=cfg.attn_block)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    return out @ params["wo"].astype(x.dtype)


def apply_cross_attn(params, cfg: ModelConfig, x, positions, kv_src,
                     src_positions):
    """Cross attention: q from x, k/v from kv_src (dense mask path)."""
    b, s, _ = x.shape
    hd = cfg.hd
    nk, nv = _kv_names(cfg)
    q = (x @ params["wq"].astype(x.dtype)).reshape(
        b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = (kv_src @ params[nk].astype(x.dtype)).reshape(
        b, kv_src.shape[1], cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = (kv_src @ params[nv].astype(x.dtype)).reshape(
        b, kv_src.shape[1], cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    out = attention(q, k, v, impl="dense_masked", causal=False)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * hd)
    return out @ params["wo"].astype(x.dtype)


def attn_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """Ring-buffered when windowed: physical length min(max_len, window)."""
    t = min(max_len, cfg.window) if cfg.window > 0 else max_len
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, t, cfg.hd), dtype),
        "v": jnp.zeros((batch, cfg.n_kv_heads, t, cfg.hd), dtype),
    }


def apply_attn_decode(params, cfg: ModelConfig, x, cache, pos):
    """One-token decode. x: (B, 1, D); pos: (B,) absolute position.

    Returns (out (B, 1, D), new_cache).
    """
    b = x.shape[0]
    hd = cfg.hd
    nk, nv = _kv_names(cfg)
    q = (x[:, 0] @ params["wq"].astype(x.dtype))
    k = (x[:, 0] @ params[nk].astype(x.dtype))
    v = (x[:, 0] @ params[nv].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, cfg.n_heads, hd)
    k = k.reshape(b, cfg.n_kv_heads, hd)
    v = v.reshape(b, cfg.n_kv_heads, hd)
    q = rope(q[:, :, None, :], pos[:, None, None], cfg.rope_theta)[:, :, 0]
    k = rope(k[:, :, None, :], pos[:, None, None], cfg.rope_theta)[:, :, 0]
    t = cache["k"].shape[2]
    slot = jnp.where(jnp.asarray(cfg.window > 0), pos % t,
                     jnp.minimum(pos, t - 1))
    bidx = jnp.arange(b)
    k_cache = cache["k"].at[bidx, :, slot].set(k.astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, :, slot].set(v.astype(cache["v"].dtype))
    valid = jnp.minimum(pos + 1, t)
    out = decode_attention(q, k_cache, v_cache, valid)
    out = out.reshape(b, 1, cfg.n_heads * hd)
    return out @ params["wo"].astype(x.dtype), {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank KV cache, weight-absorbed decode
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    m: MLACfg = cfg.mla
    ks = jax.random.split(key, 6)
    qk = m.qk_nope_dim + m.qk_rope_dim
    return {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.n_heads * qk)),
        "wkv_a": dense_init(ks[1], (cfg.d_model, m.kv_lora_rank)),
        "wk_rope": dense_init(ks[2], (cfg.d_model, m.qk_rope_dim)),
        "wk_b": dense_init(ks[3], (m.kv_lora_rank,
                                   cfg.n_heads * m.qk_nope_dim)),
        "wv_b": dense_init(ks[4], (m.kv_lora_rank,
                                   cfg.n_heads * m.v_head_dim)),
        "wo": dense_init(ks[5], (cfg.n_heads * m.v_head_dim, cfg.d_model)),
    }


def apply_mla(params, cfg: ModelConfig, x, positions, *, causal=True):
    m: MLACfg = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q = (x @ params["wq"].astype(x.dtype)).reshape(
        b, s, h, m.qk_nope_dim + m.qk_rope_dim).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = rope(q_rope, positions[:, None, :], cfg.rope_theta)
    kv_c = x @ params["wkv_a"].astype(x.dtype)              # (B, S, r)
    k_rope = rope((x @ params["wk_rope"].astype(x.dtype))[:, None],
                  positions[:, None, :], cfg.rope_theta)    # (B, 1, S, dr)
    k_nope = (kv_c @ params["wk_b"].astype(x.dtype)).reshape(
        b, s, h, m.qk_nope_dim).transpose(0, 2, 1, 3)
    v = (kv_c @ params["wv_b"].astype(x.dtype)).reshape(
        b, s, h, m.v_head_dim).transpose(0, 2, 1, 3)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    kf = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, h, s, m.qk_rope_dim))],
        axis=-1)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    out = attention(qf, kf, v, impl=cfg.attn_impl, causal=causal,
                    scale=scale, block=cfg.attn_block)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * m.v_head_dim)
    return out @ params["wo"].astype(x.dtype)


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {
        "kv_c": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
    }


def apply_mla_decode(params, cfg: ModelConfig, x, cache, pos):
    """Weight-absorbed MLA decode: attention runs in the latent space, so
    the cache is rank-(kv_lora+rope) per token instead of 2*H*hd."""
    m: MLACfg = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    q = (x[:, 0] @ params["wq"].astype(x.dtype)).reshape(
        b, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = rope(q_rope[:, :, None], pos[:, None, None],
                  cfg.rope_theta)[:, :, 0]
    kv_c_new = x[:, 0] @ params["wkv_a"].astype(x.dtype)     # (B, r)
    k_rope_new = rope((x[:, 0] @ params["wk_rope"].astype(x.dtype))
                      [:, None, None], pos[:, None, None],
                      cfg.rope_theta)[:, 0, 0]
    t = cache["kv_c"].shape[1]
    bidx = jnp.arange(b)
    slot = jnp.minimum(pos, t - 1)
    kv_c_new = shard(kv_c_new, DP, None)
    k_rope_new = shard(k_rope_new, DP, None)
    kv_c = cache["kv_c"].at[bidx, slot].set(
        kv_c_new.astype(cache["kv_c"].dtype))
    k_rope = cache["k_rope"].at[bidx, slot].set(
        k_rope_new.astype(cache["k_rope"].dtype))
    kv_c = shard(kv_c, DP, None, None)
    k_rope = shard(k_rope, DP, None, None)
    # absorb wk_b into q:  q_lat (B,H,r) = q_nope @ wk_b^T (per head)
    wk_b = params["wk_b"].astype(x.dtype).reshape(
        m.kv_lora_rank, h, m.qk_nope_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, wk_b)
    # native-dtype operands + f32 accumulation: astype(f32) materializes
    # full f32 copies of the latent cache every layer (§Perf cell C2)
    s_lat = jnp.einsum("bhr,btr->bht", q_lat, kv_c,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bhd,btd->bht", q_rope, k_rope,
                        preferred_element_type=jnp.float32)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    s = shard((s_lat + s_rope) * scale, DP, TP, None)
    valid = (jnp.arange(t)[None, :] <= pos[:, None])
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = p.astype(jnp.promote_types(kv_c.dtype, jnp.bfloat16))
    o_lat = jnp.einsum("bht,btr->bhr", p, kv_c,
                       preferred_element_type=jnp.float32)
    wv_b = params["wv_b"].astype(x.dtype).reshape(
        m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", o_lat.astype(x.dtype), wv_b)
    out = out.reshape(b, 1, h * m.v_head_dim)
    return out @ params["wo"].astype(x.dtype), \
        {"kv_c": kv_c, "k_rope": k_rope}


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {
            "w_gate": dense_init(ks[0], (cfg.d_model, d_ff)),
            "w_up": dense_init(ks[1], (cfg.d_model, d_ff)),
            "w_down": dense_init(ks[2], (d_ff, cfg.d_model)),
        }
    return {
        "w_up": dense_init(ks[0], (cfg.d_model, d_ff)),
        "w_down": dense_init(ks[1], (d_ff, cfg.d_model)),
        "b_up": jnp.zeros((d_ff,), jnp.float32),
        "b_down": jnp.zeros((cfg.d_model,), jnp.float32),
    }


def apply_mlp(params, cfg: ModelConfig, x):
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"].astype(x.dtype)) * (
            x @ params["w_up"].astype(x.dtype))
        h = shard(h, DP, None, TP)
        return h @ params["w_down"].astype(x.dtype)
    h = jax.nn.gelu(x @ params["w_up"].astype(x.dtype)
                    + params["b_up"].astype(x.dtype), approximate=True)
    h = shard(h, DP, None, TP)
    return h @ params["w_down"].astype(x.dtype) + \
        params["b_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE: top-k routing, sort-based grouped GEMM (ragged_dot)
# ---------------------------------------------------------------------------
#
# The dispatch IS a masked product (DESIGN.md §4): the routing assignment is
# a sparse mask over (token, expert); sorting tokens by expert materializes
# the mask's worklist (the same symbolic phase as the tile kernels), and
# ragged_dot executes only the admitted products — a dropless masked SpGEMM.


def init_moe(key, cfg: ModelConfig):
    mo: MoECfg = cfg.moe
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (cfg.d_model, mo.n_experts), scale=0.1),
        "experts_gate": dense_init(ks[1], (mo.n_experts, cfg.d_model,
                                           mo.d_ff_expert)),
        "experts_up": dense_init(ks[2], (mo.n_experts, cfg.d_model,
                                         mo.d_ff_expert)),
        "experts_down": dense_init(ks[3], (mo.n_experts, mo.d_ff_expert,
                                           cfg.d_model)),
    }
    if mo.n_shared:
        p["shared"] = init_mlp(ks[4], cfg,
                               d_ff=mo.d_ff_shared * mo.n_shared)
    return p


def apply_moe(params, cfg: ModelConfig, x):
    """x: (B, S, D) -> (B, S, D).

    Two paths:
    * **EP (shard_map)** when a mesh with a "model" axis is ambient: experts
      live sharded on the model axis; every rank routes its dp-shard's
      tokens, keeps only assignments that hit its local experts (a fixed
      per-rank capacity), runs the local grouped GEMM (ragged_dot) and
      psums the combine.  The routing mask's worklist is materialized
      locally — the masked-SpGEMM schedule at expert granularity — and no
      token array is ever replicated across ranks (the GSPMD dense path
      replicated the (T·k, D) gather per rank: ~1 TB/device at train_4k).
    * **dense fallback** (no mesh / ep=False): dropless sort + ragged_dot.
    """
    from .common import _mesh_axis_names
    mo: MoECfg = cfg.moe
    names = _mesh_axis_names()
    if mo.ep and "model" in names:
        return _apply_moe_ep(params, cfg, x, names)
    return _apply_moe_dense(params, cfg, x)


def _apply_moe_dense(params, cfg: ModelConfig, x):
    mo: MoECfg = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = (xt @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, mo.top_k)        # (T, k)
    if mo.router_scale:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    T = xt.shape[0]
    flat_e = top_e.reshape(-1)                           # (T*k,)
    flat_w = top_w.reshape(-1)
    src = jnp.repeat(jnp.arange(T), mo.top_k)
    order = jnp.argsort(flat_e)                          # worklist by expert
    gathered = xt[src[order]]                            # (T*k, D)
    group_sizes = jnp.bincount(flat_e, length=mo.n_experts).astype(jnp.int32)

    def ragged(lhs, rhs):
        return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), group_sizes)

    h = jax.nn.silu(ragged(gathered, params["experts_gate"])) * \
        ragged(gathered, params["experts_up"])
    out_sorted = ragged(h, params["experts_down"])       # (T*k, D)
    # combine: unsort + weight + segment-sum back onto tokens
    contrib = out_sorted * flat_w[order][:, None].astype(out_sorted.dtype)
    out = jnp.zeros((T, d), contrib.dtype).at[src[order]].add(contrib)
    out = out.reshape(b, s, d)
    if mo.n_shared:
        out = out + apply_mlp(params["shared"], cfg, x)
    return out.astype(x.dtype)


def _apply_moe_ep(params, cfg: ModelConfig, x, axis_names):
    """Expert-parallel MoE: shard_map over (dp..., model)."""
    mo: MoECfg = cfg.moe
    b, s, d = x.shape
    mesh = jax.sharding.get_abstract_mesh()
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in ("pod", "data") if a in axis_names)
    ep = mesh.shape["model"]
    if mo.n_experts % ep:
        return _apply_moe_dense(params, cfg, x)
    e_local = mo.n_experts // ep
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if dp_size and b % dp_size:
        return _apply_moe_dense(params, cfg, x)
    t_local = max(1, (b // max(dp_size, 1)) * s)
    # fixed per-rank capacity (in token-assignments)
    cap = int(np.ceil(t_local * mo.top_k / ep * mo.capacity_factor))
    cap = min(cap, t_local * mo.top_k)

    def local(xt, router, eg, eu, ed):
        # xt: (b_loc, s, d) this dp shard (replicated over model)
        bl = xt.shape[0]
        xt = xt.reshape(bl * s, d)
        T = xt.shape[0]
        rank = jax.lax.axis_index("model")
        logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, mo.top_k)
        if mo.router_scale:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        flat_e = top_e.reshape(-1)
        flat_w = top_w.reshape(-1)
        src = jnp.repeat(jnp.arange(T), mo.top_k)
        mine = (flat_e // e_local) == rank
        # one argsort: local assignments grouped by expert, others pushed out
        key = jnp.where(mine, flat_e, mo.n_experts)
        order = jnp.argsort(key)
        sel = order[:cap]
        valid = mine[sel]
        rows = src[sel]
        gathered = xt[rows] * valid[:, None].astype(xt.dtype)
        le = jnp.where(valid, flat_e[sel] - rank * e_local, e_local)
        group_sizes = jnp.bincount(le, length=e_local + 1)[:e_local]
        group_sizes = group_sizes.astype(jnp.int32)

        def ragged(lhs, rhs):
            return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype),
                                      group_sizes)

        h = jax.nn.silu(ragged(gathered, eg)) * ragged(gathered, eu)
        out_rows = ragged(h, ed)
        out_rows = out_rows * (flat_w[sel][:, None] *
                               valid[:, None]).astype(out_rows.dtype)
        combined = jnp.zeros((T, d), out_rows.dtype).at[rows].add(
            out_rows, mode="drop")
        combined = jax.lax.psum(combined, "model")
        return combined.reshape(bl, s, d)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp if dp else None, None, None), P(),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(dp if dp else None, None, None), check_vma=False)
    out = fn(x, params["router"],
             params["experts_gate"], params["experts_up"],
             params["experts_down"]).astype(x.dtype)
    if mo.n_shared:
        out = out + apply_mlp(params["shared"], cfg, x)
    return out


def moe_aux_loss(params, cfg: ModelConfig, x):
    """Load-balance auxiliary loss (Switch-style)."""
    mo = cfg.moe
    logits = (x.reshape(-1, x.shape[-1]) @
              params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_e = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top_e, mo.n_experts), axis=0)
    imp = jnp.mean(probs, axis=0)
    return mo.n_experts * jnp.sum(frac * imp)
