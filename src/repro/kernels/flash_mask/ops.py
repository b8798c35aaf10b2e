"""Public masked-attention op: schedule cache + batch/head vmap + GQA.

``flash_mask_attention`` is the runtime TPU path (Pallas; interpret=True on
CPU).  The XLA paths live in ``attention`` (they express the same
block-skipping at XLA level).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import caches

from .kernel import flash_mask_kernel, build_schedule


@functools.lru_cache(maxsize=caches.env_capacity("REPRO_FLASH_SCHED_CAP",
                                                 256))
def _sched(s_q, s_k, bq, bk, causal, window, prefix, q_offset):
    qi, ki, flags = build_schedule(s_q, s_k, bq=bq, bk=bk, causal=causal,
                                   window=window, prefix=prefix,
                                   q_offset=q_offset)
    return jnp.asarray(qi), jnp.asarray(ki), jnp.asarray(flags)


caches.register_lru("flash-sched", _sched)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "prefix", "q_offset",
                              "scale", "bq", "bk", "interpret"))
def flash_mask_attention(q, k, v, *, causal=True, window=0, prefix=0,
                         q_offset=0, scale=None, bq=128, bk=128,
                         interpret=None):
    """Masked multi-head attention, GQA-aware.

    q: (B, Hq, S, D);  k, v: (B, Hkv, T, D) with Hq % Hkv == 0.
    Returns (B, Hq, S, D) in q.dtype.
    """
    b, hq, s_q, d = q.shape
    _, hkv, s_k, _ = k.shape
    g = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    bq_ = min(bq, s_q)
    bk_ = min(bk, s_k)
    interpret = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    qi, ki, flags = _sched(s_q, s_k, bq_, bk_, causal, window, prefix,
                           q_offset)

    def one(qh, kh, vh):  # (S, D), (T, D), (T, D)
        return flash_mask_kernel(qh, kh, vh, qi, ki, flags, bq=bq_, bk=bk_,
                                 scale=scale, causal=causal, window=window,
                                 prefix=prefix, q_offset=q_offset,
                                 interpret=interpret)

    qg = q.reshape(b, hkv, g, s_q, d)
    f = jax.vmap(jax.vmap(jax.vmap(one, in_axes=(0, None, None)),
                          in_axes=(0, 0, 0)), in_axes=(0, 0, 0))
    out = f(qg, k, v)                      # (B, Hkv, G, S, D)
    return out.reshape(b, hq, s_q, d)
