"""Training substrate: optimizer, pipeline, checkpointing, compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.data.pipeline import SyntheticLM, batch_for
from repro.optim.adamw import AdamW, zero1_specs
from repro.train.checkpoint import CheckpointManager
from repro.train.train_step import init_state, make_train_step
from repro.models.common import make_param_specs


def test_adamw_converges_quadratic():
    opt = AdamW(lr=0.1, warmup=1, total_steps=200, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    target = jnp.asarray([1.0, 1.0])
    for _ in range(200):
        g = {"w": 2 * (params["w"] - target)}
        params, state, _ = opt.update(g, state, params)
    np.testing.assert_allclose(np.asarray(params["w"]), [1.0, 1.0],
                               atol=1e-2)


def test_pipeline_deterministic_and_elastic():
    pipe = SyntheticLM(vocab_size=97, seq_len=16, global_batch=8, seed=3)
    a = pipe.global_batch_at(5)
    b = pipe.global_batch_at(5)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, pipe.global_batch_at(6))
    # elastic: 2-shard and 4-shard views tile the same global batch
    s0 = pipe.shard_at(5, 0, 2)
    s1 = pipe.shard_at(5, 1, 2)
    np.testing.assert_array_equal(np.concatenate([s0, s1]), a)
    quarters = [pipe.shard_at(5, i, 4) for i in range(4)]
    np.testing.assert_array_equal(np.concatenate(quarters), a)


def test_train_loss_decreases():
    cfg = get_config("llama3_2_1b", smoke=True)
    opt = AdamW(lr=3e-3, warmup=5, total_steps=60, weight_decay=0.0)
    state = init_state(cfg, jax.random.PRNGKey(0), opt)
    pipe = SyntheticLM(cfg.vocab_size, 32, 4, seed=0, copy_frac=1.0)
    step = jax.jit(make_train_step(cfg, opt))
    losses = []
    for i in range(30):
        state, m = step(state, batch_for(cfg, pipe, i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses


def test_microbatched_grads_match():
    cfg = get_config("llama3_2_1b", smoke=True)
    opt = AdamW(lr=1e-3, warmup=1, total_steps=10)
    state = init_state(cfg, jax.random.PRNGKey(0), opt)
    pipe = SyntheticLM(cfg.vocab_size, 16, 4, seed=1)
    b = batch_for(cfg, pipe, 0)
    s1, m1 = jax.jit(make_train_step(cfg, opt))(state, b)
    s2, m2 = jax.jit(make_train_step(cfg, opt, microbatches=2))(state, b)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    a = jax.tree.leaves(s1.params)
    c = jax.tree.leaves(s2.params)
    for x, y in zip(a, c):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=2e-4,
                                   atol=2e-5)


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    mgr.save(10, tree)
    mgr.save(20, tree)
    mgr.save(30, jax.tree.map(lambda x: x * 2, tree), asynchronous=True)
    mgr.wait()
    assert mgr.latest_step() == 30
    assert mgr.all_steps() == [20, 30]       # keep=2 gc'd step 10
    like = jax.eval_shape(lambda: tree)
    out = mgr.restore(30, like)
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(tree["a"]) * 2)


def test_checkpoint_atomic_on_partial_write(tmp_path):
    """A stale .tmp directory must not shadow a published checkpoint."""
    mgr = CheckpointManager(str(tmp_path))
    tree = {"a": jnp.ones((3,))}
    mgr.save(1, tree)
    os.makedirs(os.path.join(str(tmp_path), "step_2.tmp"))  # crash artifact
    assert mgr.latest_step() == 1
    out = mgr.restore(1, jax.eval_shape(lambda: tree))
    np.testing.assert_array_equal(np.asarray(out["a"]), np.ones((3,)))


def test_zero1_specs():
    params = {"layers": {"wq": jnp.zeros((4, 64, 32))},
              "embed": jnp.zeros((100, 64))}
    specs = make_param_specs(params)
    z = zero1_specs(params, specs)
    # wq: (L, d, ff) spec (None, None, model) -> zero1 adds data on dim 1
    assert z["layers"]["wq"] == jax.sharding.PartitionSpec(
        None, "data", "model")
    assert z["embed"][0] == "model" and z["embed"][1] == "data"


def test_compression_error_feedback():
    """int8 EF all-reduce: mean error stays bounded, carry compensates."""
    from repro.train.compression import allreduce_compressed, init_error
    import jax
    mesh = jax.make_mesh((1,), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(0)
                          .standard_normal((64,)) * 0.1, jnp.float32)}
    e = init_error(g)

    def local(gw, ew):
        out, new_e = allreduce_compressed({"w": gw}, {"w": ew}, ("data",))
        return out["w"], new_e["w"]

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(jax.sharding.PartitionSpec(),) * 2,
                      out_specs=(jax.sharding.PartitionSpec(),) * 2,
                      check_vma=False)
    got, err = f(g["w"], e["w"])
    # single device: dequantized value + error == original exactly
    np.testing.assert_allclose(np.asarray(got) + np.asarray(err),
                               np.asarray(g["w"]), rtol=1e-6, atol=1e-7)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127
    assert float(jnp.abs(err).max()) <= scale / 2 + 1e-8
