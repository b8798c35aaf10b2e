"""Shared model utilities: sharding helpers, norms, RoPE, initializers."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

DP = ("pod", "data")     # data-parallel axes (logical); absent axes dropped
TP = "model"             # tensor/expert-parallel axis


def _mesh_axis_names():
    try:
        mesh = jax.sharding.get_abstract_mesh()
        return tuple(mesh.axis_names) if mesh is not None else ()
    except Exception:
        return ()


def _filter_spec(entries, axis_names) -> P:
    out = []
    for e in entries:
        if e is None:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a in axis_names)
            out.append(kept if kept else None)
        else:
            out.append(e if e in axis_names else None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard(x, *entries):
    """with_sharding_constraint that degrades to a no-op off-mesh.

    Axis names not present in the current mesh are dropped, so the same
    model code runs in single-device smoke tests, the 16x16 pod, and the
    2x16x16 multi-pod mesh.
    """
    names = _mesh_axis_names()
    if not names:
        return x
    spec = _filter_spec(entries, names)
    return jax.lax.with_sharding_constraint(x, spec)


def shard_dp(x):
    """Batch-leading activation: (B, ...) -> shard batch over DP axes."""
    return shard(x, DP)


def filter_pspec(spec, mesh):
    """Drop axis names a given mesh doesn't have (pod vs single-pod)."""
    return _filter_spec(tuple(spec), tuple(mesh.axis_names))


def fit_spec(spec, shape, mesh) -> P:
    """Make a PartitionSpec legal for a concrete (shape, mesh):

    * axis names missing from the mesh are dropped (pod on single-pod);
    * an entry whose mesh-axis product does not divide its dim is moved to
      the next free dim that divides (later dims first), else dropped.

    jit input shardings require exact divisibility, unlike internal
    with_sharding_constraint (which pads) — this is the one place sharding
    legality is decided, so every jit boundary routes through here.
    """
    sizes = dict(mesh.shape)

    def norm(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in sizes)
            return kept if kept else None
        return e if e in sizes else None

    def axsize(e):
        if isinstance(e, tuple):
            n = 1
            for a in e:
                n *= sizes[a]
            return n
        return sizes[e]

    entries = [norm(e) for e in tuple(spec)]
    entries += [None] * (len(shape) - len(entries))
    out = [None] * len(shape)
    for i, e in enumerate(entries):
        if e is None:
            continue
        n = axsize(e)
        if n <= 1:
            continue
        for j in [i] + list(range(i + 1, len(shape))) + \
                list(range(i - 1, -1, -1)):
            if out[j] is None and shape[j] % n == 0 and shape[j] >= n:
                out[j] = e
                break
    return P(*out)


def shardings_for(mesh, spec_tree, shape_tree):
    """NamedSharding pytree: fit_spec applied leaf-wise."""
    from jax.sharding import NamedSharding
    return jax.tree.map(
        lambda s, l: NamedSharding(mesh, fit_spec(s, l.shape, mesh)),
        spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P))


_UNROLL_CAP = 48


def pscan(f, init, xs, length=None):
    """lax.scan that fully unrolls when REPRO_UNROLL=1 (roofline mode).

    XLA's HloCostAnalysis visits a while-loop body ONCE, so flop/byte/
    collective counts of scanned layers are undercounted by the trip count.
    The accounting pass therefore lowers reduced-depth configs with this
    unrolled form (layer scans and attention entry scans unroll; trip
    counts above _UNROLL_CAP — SSM/mLSTM cross-chunk state scans, sLSTM's
    per-token scan — stay rolled: their bodies are the cheap state-decay
    updates, a few percent of layer flops, noted in EXPERIMENTS.md).
    """
    import os as _os
    unroll: Any = 1
    if _os.environ.get("REPRO_UNROLL") == "1":
        n = length
        if n is None and xs is not None:
            n = jax.tree.leaves(xs)[0].shape[0]
        cap = int(_os.environ.get("REPRO_UNROLL_CAP", _UNROLL_CAP))
        if n is not None and n <= cap:
            unroll = True
    return jax.lax.scan(f, init, xs, length=length, unroll=unroll)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale + bias).astype(x.dtype)


def rope(x, positions, theta: float = 10000.0):
    """Rotary embedding. x: (..., S, D) with D even; positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype=jnp.float32, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = (scale if scale is not None else 1.0) / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# parameter sharding specs by path convention
# ---------------------------------------------------------------------------

_RULES = (
    # (substring, ndim -> spec entries applied to the TRAILING dims)
    ("embed",   {2: (TP, None)}),                    # (V, D) vocab on TP
    ("lm_head", {2: (None, TP)}),                    # (D, V)
    ("patch_proj", {2: (None, None)}),
    ("wq",      {2: (None, TP)}),
    ("wk_rep",  {2: (None, None)}),
    ("wv_rep",  {2: (None, None)}),
    # MLA latent projections are small and feed the shared low-rank cache:
    # TP-sharding them propagates r-sharding into the cache and forces a
    # full-cache all-gather per layer per decode step (§Perf cell C)
    ("wkv_a",   {2: (None, None)}),
    ("wk_rope", {2: (None, None)}),
    ("wk",      {2: (None, TP)}),
    ("wv",      {2: (None, TP)}),
    ("wkv",     {2: (None, TP)}),
    ("wo",      {2: (TP, None)}),
    ("w_gate",  {2: (None, TP)}),
    ("w_up",    {2: (None, TP)}),
    ("w_down",  {2: (TP, None)}),
    ("experts", {3: (TP, None, None)}),              # (E, d, f) experts on TP
    ("router",  {2: (None, None)}),
    ("in_proj", {2: (None, TP)}),                    # ssm/xlstm big in-proj
    ("out_proj", {2: (TP, None)}),
    ("conv",    {2: (None, None), 3: (None, None, None)}),
)


def spec_for(path: str, ndim: int, stacked: bool) -> P:
    """Sharding spec for a parameter, by name convention.

    ``stacked`` marks scan-stacked params (leading layer dim -> None).
    """
    trailing = ndim - (1 if stacked else 0)
    entries: Tuple = ()
    for needle, table in _RULES:
        if needle in path and trailing in table:
            entries = table[trailing]
            break
    else:
        entries = (None,) * trailing
    full = ((None,) if stacked else ()) + tuple(entries)
    return P(*full)


def tree_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def make_param_specs(params, stacked_prefixes: Sequence[str] = ("layers",
                                                                "blocks")):
    """Pytree of PartitionSpecs parallel to ``params`` (path-convention)."""
    def one(path, leaf):
        p = jax.tree_util.keystr(path)
        stacked = any(s in p for s in stacked_prefixes) and leaf.ndim >= 2
        return spec_for(p, leaf.ndim, stacked)
    return jax.tree_util.tree_map_with_path(one, params)
