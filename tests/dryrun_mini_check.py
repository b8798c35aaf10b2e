"""Subprocess: lower+compile smoke configs on a (pod,data,model) mini-mesh
through the SAME spec machinery as the production dry-run."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.base import get_config, ShapeCfg  # noqa: E402
from repro.launch.dryrun import cache_specs, collective_bytes  # noqa: E402
from repro.launch.specs import (train_input_specs,  # noqa: E402
                                decode_input_specs)
from repro.models import transformer as T  # noqa: E402
from repro.models.common import (make_param_specs,  # noqa: E402
                                 shardings_for)
from repro.optim.adamw import AdamW  # noqa: E402
from repro.serve.decode import make_serve_step  # noqa: E402
from repro.train.train_step import (init_state, state_specs,  # noqa: E402
                                    batch_specs, make_train_step)

ARCHS = ["llama3_2_3b", "zamba2_7b", "moonshot_v1_16b_a3b",
         "deepseek_v2_lite_16b", "xlstm_1_3b", "seamless_m4t_large_v2",
         "internvl2_2b"]


def main():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    shape = ShapeCfg("mini", 64, 8, "train")
    dshape = ShapeCfg("mini_dec", 64, 8, "decode")
    opt = AdamW()
    for arch in ARCHS:
        cfg = get_config(arch, smoke=True)
        with jax.set_mesh(mesh):
            # train
            state_shapes = jax.eval_shape(
                lambda: init_state(cfg, jax.random.PRNGKey(0), opt))
            sspec = state_specs(cfg, state_shapes)
            bshapes = train_input_specs(cfg, shape)
            bspec = batch_specs(bshapes)
            ssh = shardings_for(mesh, sspec, state_shapes)
            bsh = shardings_for(mesh, bspec, bshapes)
            fn = make_train_step(cfg, opt)
            c = jax.jit(fn, in_shardings=(ssh, bsh),
                        out_shardings=(ssh, None),
                        donate_argnums=(0,)).lower(
                state_shapes, bshapes).compile()
            assert c.memory_analysis() is not None
            hlo = c.as_text()
            coll = collective_bytes(hlo)
            assert sum(coll.values()) > 0, f"{arch}: no collectives?!"

            # decode
            pshapes = jax.eval_shape(
                lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
            pspec = make_param_specs(pshapes)
            d = decode_input_specs(cfg, dshape)
            cspec = cache_specs(d["cache"])
            serve = make_serve_step(cfg)
            args = [pshapes, d["token"], d["cache"], d["pos"]]
            csh = shardings_for(mesh, cspec, d["cache"])
            in_sh = [shardings_for(mesh, pspec, pshapes),
                     shardings_for(mesh, P(("pod", "data")), d["token"]),
                     csh,
                     shardings_for(mesh, P(("pod", "data")), d["pos"])]
            if cfg.family == "audio":
                args.append(d["encoder_out"])
                in_sh.append(shardings_for(
                    mesh, P(("pod", "data"), None, None),
                    d["encoder_out"]))
            jax.jit(serve, in_shardings=tuple(in_sh),
                    out_shardings=(None, csh),
                    donate_argnums=(2,)).lower(*args).compile()
        print("OK", arch, flush=True)
    print("MINI_DRYRUN_OK")


if __name__ == "__main__":
    main()
