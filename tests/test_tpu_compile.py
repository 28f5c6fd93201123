"""Compiles for a described TPU v5e, at qwen3-0.6b's published widths.

No chip is attached: the TPU compiler compiles for a topology that is only
described, from shapes alone, and refuses what the chip would refuse (VMEM,
tiling, memory, a host callback it cannot place).  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around these compiles
(an entry written without a chip cannot be read back).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.executor import PagedExecutor
from repro.core.host_attention import HostAttention
from repro.core.kv_cache import DualPool
from repro.kernels.paged_decode.ops import paged_decode_attention
from repro.models.api import get_model

CFG = get_config("qwen3-0.6b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- any failure means no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def executor(one_chip):
    """A full-width PagedExecutor whose graphs take shapes, not arrays: the
    pools it holds are a token two pages, since the compiled graphs receive
    the pool shapes as arguments."""
    model = get_model(CFG)
    params = jax.eval_shape(model.init, jax.random.key(0))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), params)
    pool = DualPool(CFG, 2, 1)
    host = HostAttention(CFG, pool.host.k, pool.host.v)
    ex = PagedExecutor(model, params, pool, host, impl="pallas")
    yield ex
    ex.close()


def _decode_args(params, sharding, pool_sharding, D=8, MP=32, pages=64):
    i32 = jnp.int32
    pool = _spec((CFG.num_layers, pages, CFG.kv_block_size, CFG.num_kv_heads,
                  CFG.head_dim), jnp.bfloat16, pool_sharding)
    return (params,
            *[_spec((D,), i32, sharding)] * 2,
            _spec((D, MP), i32, sharding),
            _spec((D,), i32, sharding),
            _spec((D,), jnp.bool_, sharding),
            *[_spec((D,), i32, sharding)] * 2,
            pool, pool)


def test_paged_decode_kernel_compiles(one_chip):
    B, H, KV, hd, P, page, MP = 32, 16, 8, 128, 512, 16, 64
    bf16 = jnp.bfloat16
    args = (_spec((B, H, hd), bf16, one_chip),
            _spec((P, page, KV, hd), bf16, one_chip),
            _spec((P, page, KV, hd), bf16, one_chip),
            _spec((B, MP), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip))
    fn = jax.jit(lambda *a: paged_decode_attention(*a, impl="pallas"))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_decode_step_compiles(executor, one_chip):
    """The whole donated-pool decode step with its per-layer ordered host
    callback and the compiled Pallas kernel, at the smoke run's bucket."""
    args = _decode_args(executor.params, one_chip, one_chip)
    compiled = executor._build_decode(8, 32).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    # the donated pools alias their outputs instead of doubling the footprint
    pool_bytes = 2 * int(np.prod(args[-1].shape)) * 2
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < pool_bytes


def test_prefill_bucket_compiles(executor, one_chip):
    B, S = 4, 512
    args = (executor.params, _spec((B, S), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip), {})
    compiled = executor._build_prefill(B, S).lower(*args).compile()
    mem = compiled.memory_analysis()
    # the bucket returns the bf16 K/V of all B*S tokens in every layer ...
    kv_bytes = 2 * CFG.num_layers * B * S * CFG.num_kv_heads * CFG.head_dim * 2
    assert mem.output_size_in_bytes >= kv_bytes
    # ... and the whole program fits one v5e's 16 GB
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9
