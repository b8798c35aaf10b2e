"""Masked attention: the block-masked XLA path, decode and the dispatcher
against the dense baseline (full scores, then the mask).

The masks are the ones decoders and encoders use: causal, GQA/MQA head
sharing, a sliding window, a bidirectional prefix, non-causal, and a query
offset (chunked prefill: queries sit at the end of a longer key history).
"""
import jax
import numpy as np
import pytest

from repro.kernels.flash_mask.attention import (attention,
                                                block_masked_attention,
                                                decode_attention,
                                                dense_masked_attention)

D = 16

#: (causal, window, prefix)
MASKS = {
    "causal": (True, 0, 0),
    "window": (True, 24, 0),
    "prefix": (True, 0, 16),
    "noncausal": (False, 0, 0),
    "window_prefix": (True, 24, 8),
}


def _qkv(hq, hkv, s_q, s_k, *, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((batch, hq, s_q, D)).astype(np.float32)
    k = rng.standard_normal((batch, hkv, s_k, D)).astype(np.float32)
    v = rng.standard_normal((batch, hkv, s_k, D)).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("heads", [(4, 4), (4, 1)], ids=["mha", "mqa"])
@pytest.mark.parametrize("s_q,s_k,q_offset,block", [
    (64, 64, 0, 16), (128, 128, 0, 32), (32, 64, 32, 16)],
    ids=["s64", "s128", "offset32"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_block_masked_matches_dense(mask, s_q, s_k, q_offset, block, heads):
    causal, window, prefix = MASKS[mask]
    q, k, v = _qkv(*heads, s_q, s_k)
    kw = dict(causal=causal, window=window, prefix=prefix,
              q_offset=q_offset)
    got = jax.jit(lambda q, k, v: block_masked_attention(
        q, k, v, bq=block, bk=block, **kw))(q, k, v)
    want = dense_masked_attention(q, k, v, **kw)
    assert got.shape == (2, heads[0], s_q, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("ragged", [False, True], ids=["full", "ragged"])
@pytest.mark.parametrize("heads", [(4, 4), (4, 2)], ids=["mha", "gqa"])
def test_decode_attention_matches_dense_last_row(heads, ragged):
    """One decode step reads the keys below ``cache_len``: the dense causal
    path's row at position ``cache_len - 1``.  Cache slots past the length
    hold keys that must not be read."""
    t = 32
    q, k, v = _qkv(*heads, t, t, batch=3)
    cache_len = np.array([t, 19, 1] if ragged else [t] * 3, np.int32)
    b, last = np.arange(3), cache_len - 1
    got = decode_attention(q[b, :, last], k, v, cache_len)  # q: (B, Hq, D)
    want = np.asarray(dense_masked_attention(q, k, v, causal=True))
    np.testing.assert_allclose(np.asarray(got), want[b, :, last],
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("impl", ["dense_masked", "block_masked", "nope"])
def test_attention_dispatch(impl):
    q, k, v = _qkv(4, 2, 64, 64)
    kw = dict(causal=True, window=24)
    if impl == "nope":
        with pytest.raises(ValueError, match="nope"):
            attention(q, k, v, impl=impl, **kw)
        return
    got = attention(q, k, v, impl=impl, block=16, **kw)
    want = dense_masked_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


def test_starcoder_window_schedule_saves_tiles():
    from repro.kernels.flash_mask.attention import _balanced_schedule
    _, _, kv, _, valid, _ = _balanced_schedule(
        512, 512, 64, 64, True, 128, 0, 0)
    dense_tiles = (512 // 64) ** 2
    assert valid.sum() < dense_tiles / 2
