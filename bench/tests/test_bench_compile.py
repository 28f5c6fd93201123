"""The benchmark's configurations compile for a described TPU v5e and fit
its memory: each config's fused decode step and its largest prefill bucket,
with its device pool, at the published widths.

No chip is attached: the TPU compiler compiles for a topology that is only
described, from shapes alone.  Nothing runs, so these tests say nothing about
results or times.  The topology is described inside a module fixture, never
at import (one process at a time may load the TPU library, and every test
worker imports this file).  The persistent compilation cache is off around
these compiles (an entry written without a chip cannot be read back).

Peak device memory is reckoned from ``memory_analysis()``: weights, the
pool, and the larger of the decode step's (at the most rows and pages set-up
warms) and the prefill's own bytes (the warmed batch with the most attention
scores, B x S x S, which dominate a prefill's memory); a
prefill's KV then lands in the pool through an undonated eager copy, which
keeps the old K (then V) array alive beside the new one (1.5 pools).
"""

import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import run, warmup, weights  # noqa: E402
from bench.loadgen import load_mix  # noqa: E402

HBM = 16e9
CONFIGS = ["qwen3-0.6b", "yi-9b-24L"]


@pytest.fixture(scope="module")
def one_chip():
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
        yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _footprint(name, one_chip):
    from repro.config import ArchConfig, EngineConfig
    from repro.core.executor import PagedExecutor
    from repro.core.host_attention import HostAttention
    from repro.core.kv_cache import DualPool
    from repro.models.api import get_model

    cfgj = run.load_config(name, rehearse=False)
    dims = run.model_dims(cfgj)
    arch = ArchConfig(
        name=name, family="dense", num_layers=dims["L"], d_model=dims["d"],
        num_heads=dims["H"], num_kv_heads=dims["KV"], head_dim=dims["hd"],
        d_ff=dims["f"], vocab_size=dims["V"], qk_norm=dims["qk_norm"],
        rope_theta=dims["theta"], rms_eps=dims["eps"], tie_embeddings=dims["tied"],
        param_dtype=dims["dtype"], activation_dtype=dims["dtype"])
    flat = {p: _spec(s, dt, one_chip) for p, (s, dt, _) in weights.leaf_specs(dims).items()}
    params = weights._nest(flat)
    w_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in flat.values())
    tiny = DualPool(arch, 2, 1)
    ex = PagedExecutor(get_model(arch), params, tiny,
                       HostAttention(arch, tiny.host.k, tiny.host.v), impl="pallas")
    try:
        eng = cfgj["engine"]
        mix = load_mix("offload_backlog")
        stand_in = SimpleNamespace(engine_cfg=EngineConfig(**eng),
                                   pool=SimpleNamespace(page_size=16))
        sh = warmup.shapes(stand_in, mix)
        P = eng["device_pool_pages"]
        pool = _spec((dims["L"], P, 16, dims["KV"], dims["hd"]), dims["dtype"], one_chip)
        pool_bytes = 2 * int(np.prod(pool.shape)) * pool.dtype.itemsize
        D, MP = max(sh["D"]), max(sh["MP"])
        i32 = jnp.int32
        dec = ex._build_decode(D, MP).lower(
            params, *[_spec((D,), i32, one_chip)] * 2, _spec((D, MP), i32, one_chip),
            _spec((D,), i32, one_chip), _spec((D,), jnp.bool_, one_chip),
            *[_spec((D,), i32, one_chip)] * 2, pool, pool).compile()
        assert "tpu_custom_call" in dec.as_text()
        dm = dec.memory_analysis()
        # the warmed prefill with the most attention scores (B x S x S)
        B, S = max(sh["prefill"], key=lambda bs: bs[0] * bs[1] * bs[1])
        pre = ex._build_prefill(B, S).lower(
            params, _spec((B, S), i32, one_chip), _spec((B,), i32, one_chip), {}).compile()
        pm = pre.memory_analysis()
    finally:
        ex.close()
    decode = w_bytes + pool_bytes + dm.temp_size_in_bytes + (
        dm.output_size_in_bytes - dm.alias_size_in_bytes)
    prefill = w_bytes + pool_bytes + pm.temp_size_in_bytes + pm.output_size_in_bytes
    scatter = w_bytes + 1.5 * pool_bytes + pm.output_size_in_bytes
    return {"weights": w_bytes, "pool": pool_bytes, "decode": decode,
            "prefill": prefill, "scatter": scatter, "D": D, "MP": MP, "B": B, "S": S}


@pytest.mark.parametrize("name", CONFIGS)
def test_config_fits_one_v5e(name, one_chip):
    f = _footprint(name, one_chip)
    print(name, {k: (f"{v / 1e9:.3f} GB" if v > 1e6 else v) for k, v in f.items()})
    assert max(f["decode"], f["prefill"], f["scatter"]) < HBM
