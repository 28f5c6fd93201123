"""Distribution layer: logical-axis rules, multi-device numerics (subprocess
with fake host devices), int8 collectives, ZeRO specs, elastic re-mesh."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from tests.conftest import run_subprocess


def test_default_rules_per_arch():
    from repro.distributed.sharding import default_rules

    class M:  # minimal mesh stub
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    r = default_rules(get_config("qwen3-32b"), M())
    assert r["heads"] == "model" and r["vocab"] == "model"
    assert r["kv_seq"] == "model"  # blocks mode (8 kv heads < 16)
    r2 = default_rules(get_config("deepseek-moe-16b"), M())
    assert r2["kv_heads"] == "model"  # 16 kv heads == axis
    r3 = default_rules(get_config("internvl2-1b"), M())
    assert r3["heads"] is None  # 14 heads < 16-way axis: replicate
    r4 = default_rules(get_config("llama4-maverick-400b-a17b"), M())
    assert r4["experts"] == "model"


def test_spec_resolution_dedupes_axes():
    from repro.distributed.sharding import ShardingContext

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ctx = ShardingContext.for_arch(get_config("qwen3-32b"), mesh)
    spec = ctx.spec(("batch", "heads", "d_ff"))  # d_ff would reuse "model"
    assert spec == P(("data",), "model", None)


def test_zero_spec_extension():
    from repro.distributed.zero import zero_spec_for

    class M:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    s = zero_spec_for(P(None, "model"), (4096, 1024), M())
    assert s == P("data", "model")
    # non-dividing dims stay put
    s2 = zero_spec_for(P(None,), (17,), M())
    assert s2 == P(None)


def test_multi_device_loss_matches_single():
    """Same params+batch: sharded 4x2 mesh loss == single-device loss."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models.api import get_model
from repro.distributed.sharding import ShardingContext, activate

cfg = get_smoke_config('qwen3-0.6b')
m = get_model(cfg)
params = m.init(jax.random.key(0))
rng = np.random.default_rng(0)
batch = {
  'tokens': jnp.asarray(rng.integers(1, 500, size=(8, 32)), jnp.int32),
  'targets': jnp.asarray(rng.integers(1, 500, size=(8, 32)), jnp.int32),
  'loss_mask': jnp.ones((8, 32), jnp.float32),
}
l0, _ = m.loss(params, batch)
mesh = jax.make_mesh((4, 2), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = ShardingContext.for_arch(cfg, mesh)
with activate(ctx):
    l1, _ = jax.jit(m.loss)(params, batch)
print('DIFF', abs(float(l0) - float(l1)))
""")
    diff = float(out.strip().split()[-1])
    assert diff < 1e-3


def test_int8_allreduce_mean_subprocess():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.collectives import int8_allreduce_mean
mesh = jax.make_mesh((8,), ('data',))
x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 32)), jnp.float32)
y = int8_allreduce_mean({'g': x}, mesh, ('data',))['g']
# replicated input -> mean == quantised identity
err = float(jnp.max(jnp.abs(y - x)))
scale = float(jnp.max(jnp.abs(x))) / 127
print('ERR', err, 'SCALE', scale)
""")
    parts = out.split()
    err, scale = float(parts[1]), float(parts[3])
    assert err <= scale * 0.75  # within half a quantisation step


def test_blocksharded_decode_multi_device():
    """Split-K decode over a real 'model' axis == contiguous oracle."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import attention as A
from repro.distributed.sharding import ShardingContext, activate
from repro.configs import get_config

cfg = get_config('qwen3-0.6b').replace(kv_shard_mode='blocks')
mesh = jax.make_mesh((2, 4), ('data', 'model'))
rng = np.random.default_rng(0)
B, S, KV, H, hd = 4, 32, 2, 4, 16
q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
kc = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
vc = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
kn = jnp.asarray(rng.normal(size=(B, KV, hd)), jnp.float32)
vn = jnp.asarray(rng.normal(size=(B, KV, hd)), jnp.float32)
lens = jnp.asarray([3, 17, 31, 8], jnp.int32)
ctx = ShardingContext.for_arch(cfg, mesh)
with activate(ctx):
    o1, kc1, vc1 = jax.jit(lambda *a: A.decode_attention_blocksharded(*a))(q, kc, vc, kn, vn, lens)
kc2, vc2 = A.write_kv(kc, vc, kn, vn, lens)
o2 = A.decode_attention(q, kc2, vc2, lens + 1)
print('DIFF', float(jnp.max(jnp.abs(o1 - o2))), float(jnp.max(jnp.abs(kc1 - kc2))))
""")
    nums = [float(x) for x in out.split()[1:3]]
    assert max(nums) < 1e-4


def test_make_local_mesh_rejects_oversized_model_axis():
    """ValueError (not a bare assert — those vanish under python -O) with a
    message that names the fix when the model axis exceeds the devices."""
    from repro.launch.mesh import make_local_mesh

    with pytest.raises(ValueError, match="exceeds the .* available device"):
        make_local_mesh(model=9999)


def test_make_local_mesh_rejects_non_dividing_model_axis():
    out = run_subprocess("""
from repro.launch.mesh import make_local_mesh
try:
    make_local_mesh(model=3)  # 8 devices, 3 does not divide
except ValueError as e:
    print('RAISED', e)
""")
    assert out.startswith("RAISED")
    assert "does not divide" in out


def test_blocksharded_decode_kv_indivisible_model_axis():
    """KV heads (2) that don't divide the model axis (8): default_rules must
    fall back to split-K (kv_seq == 'model', kv_heads replicated) and the
    sharded decode must match the contiguous oracle on a (1, 8) mesh."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import attention as A
from repro.distributed.sharding import ShardingContext, activate, default_rules
from repro.configs import get_config

cfg = get_config('qwen3-0.6b').replace(kv_shard_mode='blocks')
mesh = jax.make_mesh((1, 8), ('data', 'model'))
rules = default_rules(cfg, mesh)
assert rules['kv_seq'] == 'model', rules
assert rules['kv_heads'] is None, rules
rng = np.random.default_rng(0)
B, S, KV, H, hd = 4, 32, 2, 4, 16
q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
kc = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
vc = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
kn = jnp.asarray(rng.normal(size=(B, KV, hd)), jnp.float32)
vn = jnp.asarray(rng.normal(size=(B, KV, hd)), jnp.float32)
lens = jnp.asarray([3, 17, 31, 8], jnp.int32)
ctx = ShardingContext.for_arch(cfg, mesh)
with activate(ctx):
    o1, kc1, vc1 = jax.jit(lambda *a: A.decode_attention_blocksharded(*a))(q, kc, vc, kn, vn, lens)
kc2, vc2 = A.write_kv(kc, vc, kn, vn, lens)
o2 = A.decode_attention(q, kc2, vc2, lens + 1)
print('DIFF', float(jnp.max(jnp.abs(o1 - o2))), float(jnp.max(jnp.abs(kc1 - kc2))))
""")
    nums = [float(x) for x in out.split()[1:3]]
    assert max(nums) < 1e-4


def test_elastic_remesh_subprocess():
    """Drop a data replica mid-run: step re-lowers and numerics continue."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models.api import get_model
from repro.distributed.elastic import ElasticRunner, initial_topology, reshard_batch

cfg = get_smoke_config('qwen3-0.6b')
m = get_model(cfg)
params = m.init(jax.random.key(0))

def factory(cfg_, mesh):
    return jax.jit(m.loss)

runner = ElasticRunner(cfg, factory, initial_topology(model_axis=2))
rng = np.random.default_rng(0)
batch = {
  'tokens': rng.integers(1, 500, size=(8, 16)).astype('int32'),
  'targets': rng.integers(1, 500, size=(8, 16)).astype('int32'),
  'loss_mask': np.ones((8, 16), 'float32'),
}
b = reshard_batch(batch, runner.topo)
l0, _ = runner.run(params, b)
assert runner.topo.data == 4
runner.on_failure(replica=2)   # host died
assert runner.topo.data == 3
b2 = reshard_batch(batch, runner.topo)   # trimmed to 6 rows
l1, _ = runner.run(params, b2)
assert len(runner.relower_events) == 2
print('OK', float(l0), float(l1), runner.relower_events[-1]['data'])
""")
    assert out.startswith("OK")
    parts = out.split()
    assert np.isfinite(float(parts[1])) and np.isfinite(float(parts[2]))
    assert parts[3] == "3"


def test_seq_parallel_rules_only_for_train():
    """build_cell turns seq->model on for train, never for serve cells."""
    out = run_subprocess("""
import jax
from repro.configs import get_config
from repro.config import SHAPES_BY_NAME
from repro.launch.cells import build_cell
mesh = jax.make_mesh((2, 4), ('data', 'model'))
c_train = build_cell(get_config('qwen3-0.6b'), SHAPES_BY_NAME['train_4k'], mesh)
c_dec = build_cell(get_config('qwen3-0.6b'), SHAPES_BY_NAME['decode_32k'], mesh)
print('TRAIN', c_train.rule_overrides.get('seq'), 'DEC', (c_dec.rule_overrides or {}).get('seq'))
""")
    assert "TRAIN model" in out and "DEC None" in out
