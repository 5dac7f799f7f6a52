"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

Interpret-mode tests (test_kernels.py) cannot see what Mosaic refuses:
block shapes that do not tile, loads from the wrong memory space, too
much VMEM. These tests run the TPU compiler for a chip that is described,
not attached, at qwen2-7b widths (28 query heads, 4 KV heads, head_dim
128, bf16), and check that the kernel really is in the program
(``tpu_custom_call``). Nothing runs, so they say nothing about results.

The topology is described inside a fixture: only one process at a time
may load the TPU library, so nothing here touches it while modules are
imported.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

H, KV, D = 28, 4, 128          # qwen2-7b attention widths
POOL_PAGES = 1025              # 1024 pages + the executor's trash page
CTX = 1024                     # prefill context (gathered KV length)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache, so keep it out of the way
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# page 4 is the CLI default block size for real executors: the page is
# the pool array's own full dim, so Mosaic's (8, 128) tiling rule holds.
# Batch 64 is the benchmark cell's widest decode bucket: 1024 pages of 16
# (its longest contexts, padded to a power of two); at page 4, 2048 pages,
# since a 64 x 4096 table would not fit the chip's 1 MiB of SMEM.
@pytest.mark.parametrize("batch,page,max_pages", [
    pytest.param(1, 16, 128, id="1-16"),
    pytest.param(16, 16, 128, id="16-16"),
    pytest.param(16, 4, 512, id="16-4"),
    pytest.param(64, 16, 1024, id="64-16-1024"),
    pytest.param(64, 4, 2048, id="64-4-2048"),
])
def test_paged_decode_compiles(one_chip, batch, page, max_pages):
    pool = (POOL_PAGES, KV, page, D)

    def step(q, k, v, bt, cl):
        return ops.paged_decode_attention(q, k, v, bt, cl, use_pallas=True)

    compiled = jax.jit(step).lower(
        _spec(one_chip, (batch, H, D), jnp.bfloat16),
        _spec(one_chip, pool, jnp.bfloat16),
        _spec(one_chip, pool, jnp.bfloat16),
        _spec(one_chip, (batch, max_pages), jnp.int32),
        _spec(one_chip, (batch,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("chunk", [16, 512])
def test_chunked_prefill_compiles(one_chip, chunk):
    def step(q, k, v, q_pos, kv_pos):
        return ops.chunked_prefill_attention(q, k, v, q_pos, kv_pos,
                                             use_pallas=True)

    compiled = jax.jit(step).lower(
        _spec(one_chip, (1, chunk, H, D), jnp.bfloat16),
        _spec(one_chip, (1, CTX, KV, D), jnp.bfloat16),
        _spec(one_chip, (1, CTX, KV, D), jnp.bfloat16),
        _spec(one_chip, (1, chunk), jnp.int32),
        _spec(one_chip, (1, CTX), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
