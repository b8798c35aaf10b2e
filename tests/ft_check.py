"""Subprocess body for the fault-tolerance / elastic-restart test.

Phase "full":    8 devices, train 6 steps, checkpoint every 2 — then exit
                 ("crash") after step 4's checkpoint.
Phase "resume":  4 devices (simulated node loss), auto-resume from LATEST,
                 finish to step 6.
Phase "oracle":  8 devices, uninterrupted 6 steps.

The resumed run's post-checkpoint losses must match the oracle's exactly
(stateless data + full-state checkpoints + topology-independent restore).
"""
import os
import sys

phase = sys.argv[1]
ckpt = sys.argv[2]
n_dev = {"full": 8, "resume": 4, "oracle": 8}[phase]
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.data.pipeline import SyntheticLM, batch_for  # noqa: E402
from repro.launch.mesh import make_elastic_mesh  # noqa: E402
from repro.models.common import shardings_for  # noqa: E402
from repro.optim.adamw import AdamW  # noqa: E402
from repro.train.checkpoint import CheckpointManager  # noqa: E402
from repro.train.train_step import (init_state, state_specs,  # noqa: E402
                                    make_train_step)

STEPS = 6
CKPT_EVERY = 2
CRASH_AFTER = 4


def main():
    assert jax.device_count() == n_dev
    cfg = get_config("llama3_2_1b", smoke=True)
    opt = AdamW(lr=1e-3, warmup=2, total_steps=STEPS, weight_decay=0.0)
    pipe = SyntheticLM(cfg.vocab_size, 16, 8, seed=11)
    mesh = make_elastic_mesh(n_dev, model_parallel=2)

    with jax.set_mesh(mesh):
        state = init_state(cfg, jax.random.PRNGKey(7), opt)
        sshapes = jax.eval_shape(lambda: state)
        sspec = state_specs(cfg, sshapes, zero1=True)
        ssh = shardings_for(mesh, sspec, sshapes)
        state = jax.device_put(state, ssh)

        start = 0
        mgr = CheckpointManager(ckpt) if ckpt else None
        if phase == "resume":
            last = mgr.latest_step()
            assert last == CRASH_AFTER, f"expected ckpt at {CRASH_AFTER}," \
                f" got {last}"
            state = mgr.restore(last, sshapes, ssh)
            start = last

        step_fn = jax.jit(make_train_step(cfg, opt),
                          in_shardings=(ssh, None),
                          out_shardings=(ssh, None),
                          donate_argnums=(0,))
        for step in range(start, STEPS):
            state, m = step_fn(state, batch_for(cfg, pipe, step))
            print(f"LOSS {step} {float(m['loss']):.6f}", flush=True)
            if phase in ("full",) and (step + 1) % CKPT_EVERY == 0:
                mgr.save(step + 1, state)
            if phase == "full" and step + 1 == CRASH_AFTER:
                print("CRASH", flush=True)
                os._exit(42)       # simulated node failure (no cleanup)
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
