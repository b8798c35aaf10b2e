"""Training launcher: config -> mesh -> data -> jitted step -> checkpoints.

Usage (CPU example, smoke config):
  PYTHONPATH=src python -m repro.launch.train --arch llama3_2_3b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real cluster the same entry point runs under the production mesh
(--mesh pod|multipod); on this CPU container it uses whatever devices
exist.  Fault tolerance: every --ckpt-every steps an atomic checkpoint is
published; on restart the launcher resumes from LATEST automatically, and
the stateless data pipeline replays the exact remaining batches.
"""
from __future__ import annotations

import argparse
import time

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.data.pipeline import SyntheticLM, batch_for
from repro.launch.mesh import make_production_mesh, make_elastic_mesh
from repro.models.common import shardings_for
from repro.optim.adamw import AdamW
from repro.train.checkpoint import CheckpointManager
from repro.train.train_step import init_state, state_specs, make_train_step


def run(arch: str, *, smoke: bool, steps: int, batch: int, seq: int,
        ckpt_dir: str = "", ckpt_every: int = 50, lr: float = 3e-4,
        mesh_kind: str = "auto", microbatches: int = 1, log_every: int = 10,
        seed: int = 0, max_seconds: float = 0.0):
    cfg = get_config(arch, smoke=smoke)
    if mesh_kind == "auto":
        n = jax.device_count()
        mesh = make_elastic_mesh(n, model_parallel=min(4, n))
    else:
        mesh = make_production_mesh(multi_pod=mesh_kind == "multipod")

    opt = AdamW(lr=lr, warmup=min(20, steps // 5 + 1), total_steps=steps)
    pipe = SyntheticLM(cfg.vocab_size, seq, batch, seed=seed)

    with jax.set_mesh(mesh):
        state = init_state(cfg, jax.random.PRNGKey(seed), opt)
        sshapes = jax.eval_shape(lambda: state)
        sspec = state_specs(cfg, sshapes, zero1=True)
        ssh = shardings_for(mesh, sspec, sshapes)
        state = jax.device_put(state, ssh)

        start_step = 0
        mgr = None
        if ckpt_dir:
            mgr = CheckpointManager(ckpt_dir)
            last = mgr.latest_step()
            if last is not None:
                state = mgr.restore(last, sshapes, ssh)
                start_step = last
                print(f"[train] resumed from step {last}")

        step_fn = jax.jit(
            make_train_step(cfg, opt, microbatches=microbatches),
            in_shardings=(ssh, None),
            out_shardings=(ssh, None),
            donate_argnums=(0,))

        losses = []
        t_start = time.time()
        for step in range(start_step, steps):
            data = batch_for(cfg, pipe, step)
            state, metrics = step_fn(state, data)
            losses.append(float(metrics["loss"]))
            if step % log_every == 0 or step == steps - 1:
                dt = time.time() - t_start
                print(f"[train] step {step} loss {losses[-1]:.4f} "
                      f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)",
                      flush=True)
            if mgr and (step + 1) % ckpt_every == 0:
                mgr.save(step + 1, state, asynchronous=True)
            if max_seconds and time.time() - t_start > max_seconds:
                print(f"[train] time budget reached at step {step}")
                break
        if mgr:
            mgr.wait()
            mgr.save(min(step + 1, steps), state)
    return losses, state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="auto",
                    choices=["auto", "pod", "multipod"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-seconds", type=float, default=0.0)
    args = ap.parse_args()
    run(args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
        seq=args.seq, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        lr=args.lr, mesh_kind=args.mesh, microbatches=args.microbatches,
        seed=args.seed, max_seconds=args.max_seconds)


if __name__ == "__main__":
    main()
