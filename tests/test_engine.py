"""End-to-end engine tests: NEO offloading must be bit-identical to the pure
model (greedy), across policies, preemption, and journal recovery."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import EngineConfig
from repro.configs import get_smoke_config
from repro.core.engine import NeoEngine
from repro.core.request import RequestState
from repro.models.api import get_model


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_smoke_config("qwen3-0.6b")
    model = get_model(cfg)
    params = model.init(jax.random.key(7))
    return cfg, model, params


def oracle_decode(model, params, prompt, n):
    logits, cache = model.prefill(
        params, jnp.asarray([prompt], jnp.int32), capacity=len(prompt) + n)
    seq = [int(jnp.argmax(logits[0]))]
    for _ in range(n - 1):
        logits, cache = model.decode(params, jnp.asarray([seq[-1]], jnp.int32), cache)
        seq.append(int(jnp.argmax(logits[0])))
    return seq


@pytest.mark.parametrize("policy", ["neo", "gpu_only", "fastdecode", "simple"])
def test_engine_matches_oracle(policy, dense_setup, rng):
    cfg, model, params = dense_setup
    prompts = [list(map(int, rng.integers(1, 500, size=n))) for n in (7, 19, 26)]
    oracles = [oracle_decode(model, params, p, 8) for p in prompts]
    ecfg = EngineConfig(device_pool_pages=7, host_pool_pages=96,
                        max_batch_tokens=64, policy=policy)
    eng = NeoEngine(cfg, ecfg, params=params)
    rids = [eng.submit(p, 8) for p in prompts]
    out = eng.run_until_done(300)
    for rid, o in zip(rids, oracles):
        assert out[rid] == o, f"{policy}: rid {rid} diverged"


def test_engine_offloads_and_swaps(dense_setup, rng):
    cfg, model, params = dense_setup
    ecfg = EngineConfig(device_pool_pages=7, host_pool_pages=128,
                        max_batch_tokens=128, policy="neo")
    eng = NeoEngine(cfg, ecfg, params=params)
    for n in (24, 30, 18, 22):
        eng.submit(list(map(int, rng.integers(1, 500, size=n))), 6)
    eng.run_until_done(300)
    assert all(r.state == RequestState.FINISHED for r in eng.requests.values())
    assert eng.stats.offloaded_decodes > 0, "tight device pool must offload"
    assert eng.pool.swap_bytes > 0


def test_engine_recompute_preemption(dense_setup, rng):
    """Both pools tiny: requests must preempt+replay, results still exact."""
    cfg, model, params = dense_setup
    prompts = [list(map(int, rng.integers(1, 500, size=n))) for n in (20, 24, 22)]
    oracles = [oracle_decode(model, params, p, 10) for p in prompts]
    ecfg = EngineConfig(device_pool_pages=5, host_pool_pages=4,
                        max_batch_tokens=64, policy="neo")
    eng = NeoEngine(cfg, ecfg, params=params)
    rids = [eng.submit(p, 10) for p in prompts]
    out = eng.run_until_done(500)
    for rid, o in zip(rids, oracles):
        assert out[rid] == o


def test_engine_journal_replay(dense_setup, rng):
    cfg, model, params = dense_setup
    p = list(map(int, rng.integers(1, 500, size=11)))
    oracle = oracle_decode(model, params, p, 12)
    e1 = NeoEngine(cfg, EngineConfig(device_pool_pages=16, host_pool_pages=32),
                   params=params)
    rid = e1.submit(p, 12)
    for _ in range(5):
        e1.step(now=e1.clock + 1e-3)
    pre = list(e1.requests[rid].out_tokens)
    assert 0 < len(pre) < 12
    journal = e1.export_journal()
    # crash: fresh engine, replay journal
    e2 = NeoEngine(cfg, EngineConfig(device_pool_pages=16, host_pool_pages=32),
                   params=params)
    mapping = e2.replay_journal(journal)
    out = e2.run_until_done(200)
    assert pre + out[mapping[rid]] == oracle


def test_engine_admission_control(dense_setup):
    cfg, model, params = dense_setup
    ecfg = EngineConfig(device_pool_pages=4, host_pool_pages=4,
                        max_batch_tokens=64, policy="neo")
    eng = NeoEngine(cfg, ecfg, params=params)
    rid_big = eng.submit(list(range(1, 200)), 8)  # can never fit any pool
    rid_ok = eng.submit([1, 2, 3, 4], 4)
    eng.run_until_done(100)
    assert eng.requests[rid_big].state == RequestState.ABORTED
    assert eng.requests[rid_ok].state == RequestState.FINISHED


def test_engine_eos_stop(dense_setup, rng):
    cfg, model, params = dense_setup
    p = list(map(int, rng.integers(1, 500, size=9)))
    seq = oracle_decode(model, params, p, 6)
    eos = seq[2]  # force stop at the 3rd token
    eng = NeoEngine(cfg, EngineConfig(device_pool_pages=16, host_pool_pages=16),
                    params=params)
    rid = eng.submit(p, 6, eos_token=eos)
    out = eng.run_until_done(100)
    assert out[rid] == seq[:3]


def test_contiguous_families_engine(rng):
    """ssm/hybrid/audio run through the slot executor; scheduler degrades."""
    for arch in ("rwkv6-7b", "seamless-m4t-medium"):
        cfg = get_smoke_config(arch)
        model = get_model(cfg)
        params = model.init(jax.random.key(3))
        extras = None
        kw = {}
        if cfg.has_encoder:
            fr = rng.normal(size=(6, cfg.d_model)).astype(np.float32)
            extras = {"frames": fr}
            kw["frames"] = jnp.asarray(fr)[None]
        p = list(map(int, rng.integers(1, 500, size=8)))
        logits, cache = model.prefill(params, jnp.asarray([p], jnp.int32),
                                      capacity=32, **kw)
        seq = [int(jnp.argmax(logits[0]))]
        for _ in range(4):
            logits, cache = model.decode(params, jnp.asarray([seq[-1]], jnp.int32), cache)
            seq.append(int(jnp.argmax(logits[0])))
        eng = NeoEngine(cfg, EngineConfig(max_batch_tokens=64, policy="neo"),
                        params=params)
        rid = eng.submit(p, 5, extras=extras)
        out = eng.run_until_done(100)
        assert out[rid] == seq, arch
        assert eng.scheduler.policy == ("gpu_only" if not cfg.supports_offload
                                        else eng.scheduler.policy)


def test_int8_kv_cache_close_to_bf16():
    """§Perf "int8-kv": greedy decode with the quantised cache matches the
    full-precision cache (small logit drift allowed)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models.api import get_model

    rng = np.random.default_rng(42)  # own rng: prompt must not depend on test order
    cfg = get_smoke_config("deepseek-moe-16b")
    cfg8 = cfg.replace(kv_cache_dtype="int8", name=cfg.name + "-int8")
    m, m8 = get_model(cfg), get_model(cfg8)
    params = m.init(jax.random.key(0))
    p = list(map(int, rng.integers(1, 500, size=14)))
    toks = jnp.asarray([p], jnp.int32)
    lo, c = m.prefill(params, toks, capacity=20)
    lo8, c8 = m8.prefill(params, toks, capacity=20)
    agree = int(int(lo.argmax()) == int(lo8.argmax()))
    for _ in range(5):
        t = jnp.asarray([int(lo.argmax())], jnp.int32)
        t8 = jnp.asarray([int(lo8.argmax())], jnp.int32)
        lo, c = m.decode(params, t, c)
        lo8, c8 = m8.decode(params, t8, c8)
        agree += int(int(lo.argmax()) == int(lo8.argmax()))
    assert agree >= 5, f"only {agree}/6 greedy tokens agree"
    assert float(jnp.abs(lo - lo8).max()) < 0.5


def test_engine_with_pallas_decode_kernel(rng):
    """The engine's device decode path through the Pallas TPU kernel
    (interpret mode) must match the jnp-oracle path token for token."""
    cfg = get_smoke_config("qwen3-0.6b")
    model = get_model(cfg)
    params = model.init(jax.random.key(11))
    rng2 = np.random.default_rng(11)
    prompts = [list(map(int, rng2.integers(1, 400, size=n))) for n in (9, 14)]
    outs = {}
    for impl in ("ref", "interpret"):
        eng = NeoEngine(cfg, EngineConfig(device_pool_pages=16, host_pool_pages=32,
                                          max_batch_tokens=128, policy="neo"),
                        params=params, kernel_impl=impl)
        rids = [eng.submit(p, 4) for p in prompts]
        outs[impl] = [eng.run_until_done(100)[r] for r in rids]
    assert outs["interpret"] == outs["ref"]


def test_device_decode_impl_follows_platform():
    """Without an override the platform picks device decode attention:
    the jnp reference on the CPU (the compiled Pallas kernel on TPU), and
    never interpret mode."""
    cfg = get_smoke_config("qwen3-0.6b")
    eng = NeoEngine(cfg, EngineConfig(device_pool_pages=16, host_pool_pages=32,
                                      max_batch_tokens=128, policy="neo"))
    want = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert (eng.executor.impl, eng.executor.interpret) == (want, False)
    eng.close()
