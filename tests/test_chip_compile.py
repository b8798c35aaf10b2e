"""Compile the masked-product kernels for a described TPU v5e chip.

Interpret mode cannot show what the chip's compiler refuses: scalar
memory (SMEM) overflows, block shapes Mosaic cannot tile, programs larger
than the chip's 16 GB.  These tests compile, in this process and without a
chip, for a ``v5e:2x2`` topology described by the installed libtpu; they
run nothing.  The topology is described inside a module fixture (never at
import), which skips where it cannot be described, and the persistent
compilation cache is off around the compiles: an entry compiled for a
described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from conftest import SCALE16_TC

#: HBM of one v5e chip
V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # no compiler logs under /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("bs", [8, 32, 128])
def test_block_spgemm_compiles_past_one_call_of_smem(one_chip, bs):
    """81,920 worklist entries: as one call, the scalar-prefetch arrays
    would need 1.3 MiB of the 1 MiB SMEM (65,536 entries already fail);
    the chunked executor replays them in five calls."""
    from repro.kernels.masked_matmul.ops import (SPGEMM_CHUNK,
                                                 _block_spgemm_pallas)
    n_chunks = 5
    assert n_chunks * SPGEMM_CHUNK > 65536
    blocks = jax.ShapeDtypeStruct((4096, bs, bs), jnp.float32,
                                  sharding=one_chip)
    chunks = jax.ShapeDtypeStruct((n_chunks, 4, SPGEMM_CHUNK), jnp.int32,
                                  sharding=one_chip)
    compiled = _block_spgemm_pallas.lower(
        blocks, blocks, chunks, nnzb_out=4096, bs=bs,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ring_stage_compiles_on_four_chip_mesh(topo):
    """The sparse ring's shard program, Pallas stages included, with each
    stage's worklist longer than one kernel call."""
    from repro.core.distributed import _ring_sparse_program
    from repro.kernels.masked_matmul.ops import SPGEMM_CHUNK
    p, bs, wa, wm, pm, rows_loc, n_ex = 4, 32, 512, 512, 64, 4096, 8192
    mesh = Mesh(np.array(topo.devices[:p]), ("data",))
    sharded = NamedSharding(mesh, P("data"))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharded)

    blocks = arg((p, wa, bs, bs), jnp.float32)
    sched = arg((p, p, 3, 4, SPGEMM_CHUNK), jnp.int32)
    extract = [arg((p, n_ex), jnp.int32) for _ in range(5)]
    run = _ring_sparse_program(mesh, "data", p, bs, wm, pm, rows_loc,
                               "pallas", False)
    text = run.lower(blocks, blocks, blocks, blocks, sched,
                     *extract).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_masked_matmul_compiles_at_128(one_chip):
    from repro.kernels.masked_matmul.ops import masked_matmul
    dense = jax.ShapeDtypeStruct((1024, 1024), jnp.float32,
                                 sharding=one_chip)
    coords = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    compiled = masked_matmul.lower(dense, dense, coords, coords, bm=128,
                                   bn=128, bk=128, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scale16_row_program_fits_one_chip(one_chip):
    """The row kernel the planner elects for the scale-16 triangle count
    under the v5e's memory limit compiles at m = 65,536 and fits in HBM.
    (Without the limit it elects inner, whose per-mask-slot B^T gather
    aborts the chip's compiler, or msa, whose state needs 21 GB.)"""
    from repro.core.formats import PaddedCSR
    from repro.core.masked_spgemm import _masked_spgemm_padded
    from repro.core.planner import PlanStats, decide
    from repro.core.semiring import PLUS_TIMES
    s = PlanStats(**SCALE16_TC, complement=False, mem_limit=V5E_HBM_BYTES)
    plan = decide(s)
    assert plan.algorithm in ("hash", "mca")
    wa, wb, pm = plan.widths
    m = s.m

    def padded(width):
        return PaddedCSR(
            jax.ShapeDtypeStruct((m, width), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((m, width), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip),
            (m, s.n))

    compiled = _masked_spgemm_padded.lower(
        padded(pm), padded(wa), padded(wb), algorithm=plan.algorithm,
        sr=PLUS_TIMES, complement=False, n_inspect=None, shape=(m, s.n),
        kdim=s.k).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
