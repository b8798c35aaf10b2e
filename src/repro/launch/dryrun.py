import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (device count locks at
# first backend init).  Everything below is ordinary code.

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from typing import Dict  # noqa: E402

import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.base import (ARCH_IDS, SHAPES, get_config,  # noqa: E402
                                cell_is_runnable)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import (train_input_specs,  # noqa: E402
                                decode_input_specs)
from repro.models.common import shardings_for  # noqa: E402

DP = ("pod", "data")

# ---------------------------------------------------------------------------
# HLO collective-traffic accounting (per-device bytes, from the partitioned
# module text;  §Roofline uses: term = bytes_per_device / link_bw)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s+(\(?[a-z0-9]+\[[^=]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(sig: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device collective traffic by op kind (output-shape proxy;
    all-reduce counted 2x for the ring reduce-scatter+all-gather)."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        sig, kind = m.group(1), m.group(2)
        b = _shape_bytes(sig)
        if kind == "all-reduce":
            b *= 2
        out[kind] = out.get(kind, 0) + b
    return out


# ---------------------------------------------------------------------------
# cache/batch sharding specs
# ---------------------------------------------------------------------------

_CACHE_RULES = {
    "k": (DP, "model", None, None), "v": (DP, "model", None, None),
    # MLA latent caches: context-parallel (T over model) — each rank holds
    # 1/TP of the sequence; softmax/contraction reductions over T are the
    # small (b,h) flash-statistics collectives (§Perf C3)
    "kv_c": (DP, "model", None), "k_rope": (DP, "model", None),
    "S": (DP, "model", None, None), "conv": (DP, None, "model"),
    "C": (DP, None, "model", None), "n": (DP, None, "model"),
    "m": (DP, None), "c": (DP, None, "model"), "h": (DP, None, "model"),
}


def cache_specs(cache_shapes):
    def one(path, leaf):
        name = None
        for part in reversed(path):
            key = getattr(part, "key", None)
            if isinstance(key, str) and key in _CACHE_RULES:
                name = key
                break
        base = _CACHE_RULES.get(name, (DP,))
        lead = leaf.ndim - len(base)
        return P(*([None] * lead), *base)
    return jax.tree_util.tree_map_with_path(one, cache_shapes)


# ---------------------------------------------------------------------------
# lowering one cell
# ---------------------------------------------------------------------------


def reduced_depth_cfgs(cfg):
    """Two reduced-depth configs (cfg1, cfg2, l1, l2, l_full) preserving the
    stack pattern, for layer-extrapolated cost accounting (scan bodies are
    counted once by HloCostAnalysis; unrolled reduced-depth lowerings give
    the exact per-layer delta, and layers are homogeneous by construction).
    """
    fam = cfg.family
    if fam == "audio":
        # vary encoder+decoder pairs together
        c1 = cfg.replace(n_enc_layers=1, n_dec_layers=1)
        c2 = cfg.replace(n_enc_layers=2, n_dec_layers=2)
        return c1, c2, 1, 2, cfg.n_enc_layers
    if fam == "ssm" and cfg.xlstm is not None:
        r = cfg.xlstm.slstm_every
        return (cfg.replace(n_layers=r), cfg.replace(n_layers=2 * r),
                r, 2 * r, cfg.n_layers)
    if fam == "hybrid":
        e = cfg.hybrid_attn_every
        return (cfg.replace(n_layers=e), cfg.replace(n_layers=2 * e),
                e, 2 * e, cfg.n_layers)
    if cfg.moe is not None:
        kd = cfg.first_k_dense
        return (cfg.replace(n_layers=kd + 1), cfg.replace(n_layers=kd + 2),
                kd + 1, kd + 2, cfg.n_layers)
    return cfg.replace(n_layers=1), cfg.replace(n_layers=2), 1, 2, \
        cfg.n_layers


def account_cell(arch: str, shape_name: str, multi_pod: bool,
                 attn_impl: str = None):
    """Exact per-device cost metrics via reduced-depth unrolled lowerings:
        metric(L_full) = m1 + (m2 - m1) * (L_full - l1) / (l2 - l1)
    Returns a result dict shaped like lower_cell's, accounting="extrapolated".
    """
    cfg0 = _PATCHED_CFG.get(arch) or get_config(arch)
    c1, c2, l1, l2, l_full = reduced_depth_cfgs(cfg0)
    outer_patch = _PATCHED_CFG.get(arch)

    def metrics(res):
        ca = res.get("cost_analysis", {})
        coll = res.get("collective_bytes_per_device", {})
        return (float(ca.get("flops", float("nan"))),
                float(ca.get("bytes accessed", float("nan"))),
                float(sum(v for v in coll.values()
                          if isinstance(v, (int, float)))))

    results = []
    for c in (c1, c2):
        _PATCHED_CFG[arch] = c
        try:
            results.append(lower_cell(arch, shape_name, multi_pod,
                                      attn_impl=attn_impl, unroll=True))
        finally:
            if outer_patch is not None:
                _PATCHED_CFG[arch] = outer_patch
            else:
                _PATCHED_CFG.pop(arch, None)
        if results[-1]["status"] != "ok":
            return results[-1]
    m1 = metrics(results[0])
    m2 = metrics(results[1])
    scale = (l_full - l1) / (l2 - l1)
    flops, byts, coll = (a + (b - a) * scale for a, b in zip(m1, m2))
    out = dict(results[0])
    out["accounting"] = "extrapolated"
    out["depths"] = {"l1": l1, "l2": l2, "l_full": l_full}
    out["cost_analysis"] = {"flops": flops, "bytes accessed": byts}
    out["collective_bytes_per_device"] = {"total": coll}
    out["samples"] = {"l1": m1, "l2": m2}
    return out


# not a memo: a config-override side channel for reduced-depth probe
# cells, written/restored in try/finally and bounded by the arch table
_PATCHED_CFG = {}  # lint: cache-ok(override channel, not a cache)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               attn_impl: str = None, zero1: bool = True,
               microbatches: int = 1, unroll: bool = False):
    if unroll:
        os.environ["REPRO_UNROLL"] = "1"   # exact cost accounting (pscan)
    else:
        os.environ.pop("REPRO_UNROLL", None)
    from repro.models import transformer as T
    from repro.optim.adamw import AdamW
    from repro.serve.decode import make_serve_step
    from repro.train.train_step import (init_state,
                                        state_specs,
                                        batch_specs,
                                        make_train_step)

    cfg = _PATCHED_CFG.get(arch) or get_config(arch)
    if attn_impl:
        cfg = cfg.replace(attn_impl=attn_impl)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    opt = AdamW()
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind in ("train", "prefill"):
            state_shapes = jax.eval_shape(
                lambda: init_state(cfg, jax.random.PRNGKey(0), opt))
            sspec = state_specs(cfg, state_shapes, zero1=zero1)
            bshapes = train_input_specs(cfg, shape)
            bspec = batch_specs(bshapes)
            ssh = shardings_for(mesh, sspec, state_shapes)
            bsh = shardings_for(mesh, bspec, bshapes)
            if shape.kind == "train":
                fn = make_train_step(cfg, opt, microbatches=microbatches)
                jitted = jax.jit(fn,
                                 in_shardings=(ssh, bsh),
                                 out_shardings=(ssh, None),
                                 donate_argnums=(0,))
                lowered = jitted.lower(state_shapes, bshapes)
            else:  # prefill: forward only (inference)
                def fwd(params, batch):
                    return T.forward(params, cfg, batch)
                jitted = jax.jit(fwd, in_shardings=(ssh.params, bsh))
                lowered = jitted.lower(state_shapes.params, bshapes)
        else:  # decode
            params_shapes = jax.eval_shape(
                lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
            # serve weights in activation dtype
            params_shapes = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, cfg.activation_dtype), params_shapes)
            from repro.models.common import make_param_specs
            pspec = make_param_specs(params_shapes)
            dspecs = decode_input_specs(cfg, shape)
            cspec = cache_specs(dspecs["cache"])
            serve = make_serve_step(cfg)
            args = [params_shapes, dspecs["token"], dspecs["cache"],
                    dspecs["pos"]]
            csh = shardings_for(mesh, cspec, dspecs["cache"])
            in_sh = [shardings_for(mesh, pspec, params_shapes),
                     shardings_for(mesh, P(DP), dspecs["token"]),
                     csh,
                     shardings_for(mesh, P(DP), dspecs["pos"])]
            if cfg.family == "audio":
                args.append(dspecs["encoder_out"])
                in_sh.append(shardings_for(mesh, P(DP, None, None),
                                           dspecs["encoder_out"]))
            jitted = jax.jit(serve, in_shardings=tuple(in_sh),
                             out_shardings=(None, csh),
                             donate_argnums=(2,))
            lowered = jitted.lower(*args)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem[k] = getattr(ma, k, None)
    except Exception as e:          # pragma: no cover
        mem["error"] = str(e)
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    k in ("flops", "bytes accessed", "optimal_seconds")
                    or k.startswith("bytes accessed"))}
    except Exception as e:          # pragma: no cover
        cost = {"error": str(e)}
    try:
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        n_coll_ops = {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(")
                      for k in ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute")}
    except Exception as e:          # pragma: no cover
        coll, n_coll_ops = {"error": str(e)}, {}

    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        "kind": shape.kind,
        "attn_impl": attn_impl or get_config(arch).attn_impl,
        "unrolled": unroll,
        "status": "ok",
        "seconds_lower": round(t_lower, 1),
        "seconds_compile": round(t_compile, 1),
        "memory_analysis": mem,
        "cost_analysis": cost,
        "collective_bytes_per_device": coll,
        "collective_op_counts": n_coll_ops,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll scans for exact cost accounting")
    ap.add_argument("--account", action="store_true",
                    help="layer-extrapolated exact accounting (fast)")
    ap.add_argument("--patch", default="",
                    help="config overrides, e.g. "
                         "kv_replicated=true,moe.ep=false,remat=dots")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape) in subprocesses")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    if args.all:
        os.makedirs(args.out, exist_ok=True)
        for arch in ARCH_IDS:
            for shape in SHAPES:
                suffix = ".acct" if args.account else (
                    ".unroll" if args.unroll else "")
                name = f"{arch}.{shape}.{args.mesh}{suffix}"
                path = os.path.join(args.out, name + ".json")
                if os.path.exists(path):
                    print("skip (exists):", name)
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", args.mesh,
                       "--out", args.out]
                if args.unroll:
                    cmd += ["--unroll", "--tag", "unroll"]
                if args.account:
                    cmd += ["--account", "--tag", "acct"]
                print(">>", name, flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=7200)
                if r.returncode != 0:
                    with open(os.path.join(args.out, name + ".err"),
                              "w") as f:
                        f.write(r.stdout + "\n" + r.stderr)
                    print("FAILED:", name, r.stderr.splitlines()[-1:],
                          flush=True)
        return

    assert args.arch and args.shape
    if args.patch:
        import dataclasses
        cfg = get_config(args.arch)
        sub_kw = {"moe": {}, "ssm": {}, "xlstm": {}, "mla": {}}
        top_kw = {}
        for kv in args.patch.split(","):
            k, v = kv.split("=")
            v = {"true": True, "false": False}.get(
                v, int(v) if v.lstrip("-").isdigit() else
                (float(v) if v.replace(".", "").lstrip("-").isdigit()
                 else v))
            pre = k.split(".", 1)
            if len(pre) == 2 and pre[0] in sub_kw:
                sub_kw[pre[0]][pre[1]] = v
            else:
                top_kw[k] = v
        for name, kw in sub_kw.items():
            if kw:
                top_kw[name] = dataclasses.replace(getattr(cfg, name), **kw)
        _PATCHED_CFG[args.arch] = cfg.replace(**top_kw)
    if args.account:
        res = account_cell(args.arch, args.shape, args.mesh == "multipod",
                           attn_impl=args.attn_impl)
    else:
        res = lower_cell(args.arch, args.shape, args.mesh == "multipod",
                         attn_impl=args.attn_impl, zero1=not args.no_zero1,
                         microbatches=args.microbatches, unroll=args.unroll)
    os.makedirs(args.out, exist_ok=True)
    tag = f".{args.tag}" if args.tag else ""
    name = f"{args.arch}.{args.shape}.{args.mesh}{tag}.json"
    with open(os.path.join(args.out, name), "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res, indent=2))


if __name__ == "__main__":
    main()
