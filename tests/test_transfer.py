"""Transfer engine + pipelined plan→launch→join execution tests.

Covers: async swaps preserving KV contents and free-page accounting,
pipelined vs serial greedy decode bitwise equality, dependent-decode
correctness under swap pressure, starvation-limit preemption draining a full
host pool, and the measured-overlap stats."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import EngineConfig
from repro.configs import get_smoke_config
from repro.core.engine import NeoEngine
from repro.core.kv_cache import DualPool
from repro.core.perfmodel import PerfModel
from repro.core.request import Request, RequestState
from repro.core.scheduler import NeoScheduler, PoolView
from repro.core.transfer import TransferEngine
from repro.models.api import get_model


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_smoke_config("qwen3-0.6b")
    model = get_model(cfg)
    params = model.init(jax.random.key(7))
    return cfg, model, params


def _mk_request(rid, pool: DualPool, n_pages: int, location="gpu"):
    req = Request(rid=rid, prompt=[1, 2, 3], max_new_tokens=4)
    req.state = RequestState.RUNNING
    req.location = location
    src = pool.device if location == "gpu" else pool.host
    req.pages = src.alloc(n_pages)
    return req


# ---------------------------------------------------------------------------
# TransferEngine unit tests
# ---------------------------------------------------------------------------


def test_transfer_roundtrip_preserves_kv(dense_setup):
    cfg, _, _ = dense_setup
    pool = DualPool(cfg, device_pages=8, host_pages=8)
    te = TransferEngine(pool)
    req = _mk_request(0, pool, 3)
    rng = np.random.default_rng(0)
    k = rng.normal(size=(cfg.num_attention_layers, 3, cfg.kv_block_size,
                         cfg.num_kv_heads, cfg.head_dim)).astype(np.float32)
    v = rng.normal(size=k.shape).astype(np.float32)
    pool.device.put_pages(req.pages, k, v)

    h = te.swap_out(req)
    te.join([h])
    assert req.location == "cpu"
    k_host, v_host = pool.host.read_pages(req.pages)
    np.testing.assert_allclose(k_host, k, rtol=1e-6)
    np.testing.assert_allclose(v_host, v, rtol=1e-6)
    assert te.stats.bytes_out == k_host.nbytes + v_host.nbytes
    assert pool.swap_bytes == te.stats.bytes_out

    h2 = te.swap_in(req)
    te.join([h2])
    assert req.location == "gpu"
    k_dev, v_dev = pool.device.read_pages(req.pages)
    np.testing.assert_allclose(k_dev, k, rtol=1e-6)
    np.testing.assert_allclose(v_dev, v, rtol=1e-6)
    assert te.stats.bytes_in > 0
    # free lists balanced after the round trip
    assert pool.device.free_pages == 8 - 3
    assert pool.host.free_pages == 8
    te.close()


def test_transfer_free_accounting_at_launch(dense_setup):
    """Page accounting must move at LAUNCH time (the scheduler plans against
    it), even while the copy is still in flight."""
    cfg, _, _ = dense_setup
    pool = DualPool(cfg, device_pages=6, host_pages=6)
    te = TransferEngine(pool)
    req = _mk_request(0, pool, 4)
    h = te.swap_out(req)
    # accounting is synchronous: device pages freed, host pages allocated
    assert pool.device.free_pages == 6
    assert pool.host.free_pages == 2
    assert req.location == "cpu"
    te.join([h])
    te.drain()
    te.close()


def test_transfer_empty_request(dense_setup):
    cfg, _, _ = dense_setup
    pool = DualPool(cfg, device_pages=2, host_pages=2)
    te = TransferEngine(pool)
    req = Request(rid=0, prompt=[1], max_new_tokens=1)
    h = te.swap_out(req)
    assert h.done() and req.location == "cpu"
    h2 = te.swap_in(req)
    assert h2.done() and req.location == "gpu"
    te.close()


def test_close_is_idempotent_and_joins_workers(dense_setup):
    cfg, _, _ = dense_setup
    pool = DualPool(cfg, device_pages=8, host_pages=8)
    te = TransferEngine(pool)
    req = _mk_request(0, pool, 2)
    _fill_pages(cfg, pool, req)
    h = te.swap_out(req)
    te.close()
    # draining close: the in-flight swap completed before the join
    assert h.done() and h.error is None
    for w in te._workers.values():
        assert not w.is_alive()
    te.close()  # second close is a no-op, not an error
    assert te._closed


def test_swap_after_close_raises(dense_setup):
    cfg, _, _ = dense_setup
    pool = DualPool(cfg, device_pages=8, host_pages=8)
    te = TransferEngine(pool)
    te.close()
    req = _mk_request(1, pool, 1)
    with pytest.raises(RuntimeError, match="closed"):
        te.swap_out(req)
    with pytest.raises(RuntimeError, match="closed"):
        te.swap_in(req)
    with pytest.raises(RuntimeError, match="closed"):
        te.copy_pages([0], "gpu", "cpu")


def test_close_survives_failed_transfer(dense_setup):
    """A job that raised in flight must not wedge close(): the error is
    re-raised only after every queue is drained and every worker joined."""
    cfg, _, _ = dense_setup
    pool = DualPool(cfg, device_pages=8, host_pages=8)
    te = TransferEngine(pool)
    req = _mk_request(0, pool, 2)
    _fill_pages(cfg, pool, req)
    h = te.swap_out(req)
    te.join([h])
    boom = RuntimeError("injected copy failure")
    bad = te.swap_in(req)
    bad._event.wait(5.0)  # let the gather finish before poisoning
    bad.error = boom
    with pytest.raises(RuntimeError, match="injected copy failure"):
        te.close()
    assert te._closed
    for w in te._workers.values():
        assert not w.is_alive()


def _fill_pages(cfg, pool, req, seed=0, location="gpu"):
    rng = np.random.default_rng(seed)
    shape = (cfg.num_attention_layers, len(req.pages), cfg.kv_block_size,
             cfg.num_kv_heads, cfg.head_dim)
    k = rng.normal(size=shape).astype(np.float32)
    v = rng.normal(size=shape).astype(np.float32)
    src = pool.device if location == "gpu" else pool.host
    src.put_pages(req.pages, k, v)
    return k, v


def test_per_direction_streams_concurrent_in_out(dense_setup):
    """A stalled device->host copy must NOT block a concurrent host->device
    swap-in: the two directions run on independent streams (full-duplex
    PCIe), whereas the legacy single worker serializes them in queue
    order."""
    import threading

    cfg, _, _ = dense_setup
    for per_direction, expect_overlap in ((True, True), (False, False)):
        pool = DualPool(cfg, device_pages=8, host_pages=8)
        te = TransferEngine(pool, per_direction=per_direction)
        req_out = _mk_request(0, pool, 3)  # device-resident, swaps out
        req_in = _mk_request(1, pool, 1, location="cpu")  # host, swaps in
        k_out, v_out = _fill_pages(cfg, pool, req_out, seed=0)
        k_in, v_in = _fill_pages(cfg, pool, req_in, seed=1, location="cpu")
        # stall the OUT copy at its byte-accounting tail until released
        # (keyed on the job's byte count so it works in both worker modes)
        release = threading.Event()
        out_nbytes = 2 * k_out.nbytes
        orig = pool.add_swap_bytes

        def stalled(n):
            if n == out_nbytes:
                release.wait(timeout=10)
            orig(n)

        pool.add_swap_bytes = stalled
        h_out = te.swap_out(req_out)  # queued first
        h_in = te.swap_in(req_in)
        if expect_overlap:
            te.join([h_in])  # completes although the out stream is stalled
            assert not h_out.done()
        else:
            # single worker: the stalled out job blocks the queued in job
            assert not h_in.wait(0.3)
        release.set()
        te.join([h_out, h_in])
        k_dev, v_dev = pool.device.read_pages(req_in.pages)
        np.testing.assert_allclose(k_dev, k_in, rtol=1e-6)
        k_host, _ = pool.host.read_pages(req_out.pages)
        np.testing.assert_allclose(k_host, k_out, rtol=1e-6)
        # per-stream busy accounting covers exactly the streams that ran
        streams = set(te.stats.busy_by_stream)
        assert streams == ({"out", "in"} if per_direction else {"all"})
        te.close()


def test_lane_scoped_join_requests(dense_setup):
    """join_requests must join exactly the pending transfers of the given
    requests (the per-lane join point), leaving the rest for drain()."""
    cfg, _, _ = dense_setup
    pool = DualPool(cfg, device_pages=8, host_pages=8)
    te = TransferEngine(pool)
    ra = _mk_request(0, pool, 2)
    rb = _mk_request(1, pool, 2)
    _fill_pages(cfg, pool, ra, 0)
    _fill_pages(cfg, pool, rb, 1)
    ha = te.swap_out(ra)
    hb = te.swap_out(rb)
    te.join_requests([ra], kind="out")
    assert ha.done()
    with te._lock:
        pending = list(te._pending)
    assert ha not in pending, "joined handle must leave the pending set"
    assert hb in pending or hb.done()
    # a kind mismatch joins nothing
    te.join_requests([rb], kind="in")
    with te._lock:
        assert hb in te._pending
    te.drain()
    with te._lock:
        assert not te._pending
    te.close()


def test_byte_accounting_matches_single_worker(dense_setup):
    """Per-direction streams must report byte-for-byte the same accounting
    as the legacy single worker over an identical swap sequence."""
    cfg, _, _ = dense_setup
    results = {}
    for per_direction in (True, False):
        pool = DualPool(cfg, device_pages=8, host_pages=8)
        te = TransferEngine(pool, per_direction=per_direction)
        r0 = _mk_request(0, pool, 3)
        _fill_pages(cfg, pool, r0, seed=3)
        te.join([te.swap_out(r0)])
        te.join([te.swap_in(r0)])
        r1 = _mk_request(1, pool, 1)
        _fill_pages(cfg, pool, r1, seed=4)
        te.join([te.swap_out(r1)])
        results[per_direction] = (te.stats.bytes_out, te.stats.bytes_in,
                                  te.stats.jobs, pool.swap_bytes)
        te.close()
    assert results[True] == results[False]


# ---------------------------------------------------------------------------
# pipelined engine end-to-end
# ---------------------------------------------------------------------------


def _oracle(model, params, prompt, n):
    logits, cache = model.prefill(
        params, jnp.asarray([prompt], jnp.int32), capacity=len(prompt) + n)
    seq = [int(jnp.argmax(logits[0]))]
    for _ in range(n - 1):
        logits, cache = model.decode(params, jnp.asarray([seq[-1]], jnp.int32), cache)
        seq.append(int(jnp.argmax(logits[0])))
    return seq


@pytest.mark.parametrize("policy", ["neo", "fastdecode"])
def test_pipelined_matches_serial_bitwise(policy, dense_setup):
    """Pipelined greedy decode (async swaps + overlapped batch-1) must be
    bitwise identical to the serial reference path AND the pure model."""
    cfg, model, params = dense_setup
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(1, 500, size=n))) for n in (9, 21, 33)]
    oracles = [_oracle(model, params, p, 7) for p in prompts]
    outs = {}
    for pipe in (True, False):
        ecfg = EngineConfig(device_pool_pages=7, host_pool_pages=96,
                            max_batch_tokens=64, policy=policy, pipeline=pipe)
        eng = NeoEngine(cfg, ecfg, params=params)
        rids = [eng.submit(p, 7) for p in prompts]
        res = eng.run_until_done(300)
        outs[pipe] = [res[r] for r in rids]
        eng.close()
    assert outs[True] == outs[False], f"{policy}: pipelined != serial"
    assert outs[True] == oracles, f"{policy}: pipelined != oracle"


def test_async_swap_completes_before_dependent_decode(dense_setup):
    """Swap-pressure workload: every decode that follows a swap must read the
    moved pages — token streams stay exact under a tiny device pool."""
    cfg, model, params = dense_setup
    rng = np.random.default_rng(11)
    prompts = [list(map(int, rng.integers(1, 500, size=n)))
               for n in (24, 30, 18, 22)]
    oracles = [_oracle(model, params, p, 6) for p in prompts]
    ecfg = EngineConfig(device_pool_pages=7, host_pool_pages=128,
                        max_batch_tokens=128, policy="neo")
    eng = NeoEngine(cfg, ecfg, params=params)
    rids = [eng.submit(p, 6) for p in prompts]
    out = eng.run_until_done(300)
    assert eng.stats.offloaded_decodes > 0, "tight device pool must offload"
    assert eng.stats.swap_out_bytes > 0
    for rid, o in zip(rids, oracles):
        assert out[rid] == o
    eng.close()


def test_pipelined_overlap_metrics(dense_setup):
    """The pipelined engine must report measured overlap: host attention
    concurrent with device dispatch and swap bytes hidden under compute."""
    cfg, model, params = dense_setup
    rng = np.random.default_rng(5)
    ecfg = EngineConfig(device_pool_pages=7, host_pool_pages=128,
                        max_batch_tokens=128, policy="neo")
    eng = NeoEngine(cfg, ecfg, params=params)
    for n in (24, 30, 18, 22, 26, 28):
        eng.submit(list(map(int, rng.integers(1, 500, size=n))), 6)
    eng.run_until_done(400)
    s = eng.stats
    assert s.pipelined_steps > 0, "no step ran both batches concurrently"
    assert s.pipeline_overlap_time > 0.0
    assert s.swap_hidden_bytes > 0
    assert s.host_busy_time > 0.0 and s.device_busy_time > 0.0
    assert 0.0 <= s.bubble_fraction <= 1.0
    eng.close()


def test_f16_host_pool_roundtrip_and_equality():
    """16-bit archs store host KV in the device pool's bfloat16: the swap
    round trip moves the device's bits unchanged, PCIe bytes count 2 per
    element, and pipelined greedy decode must still match the serial path."""
    import dataclasses

    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"), name="bf16-smoke",
                              param_dtype="bfloat16", activation_dtype="bfloat16")
    pool = DualPool(cfg, device_pages=6, host_pages=6)
    assert pool.host.k.dtype == jnp.bfloat16
    te = TransferEngine(pool)
    req = _mk_request(0, pool, 2)
    rng = np.random.default_rng(2)
    k = rng.normal(size=(cfg.num_attention_layers, 2, cfg.kv_block_size,
                         cfg.num_kv_heads, cfg.head_dim)).astype(np.float32)
    pool.device.put_pages(req.pages, k, k)
    h = te.swap_out(req)
    te.join([h])
    k_host, _ = pool.host.read_pages(req.pages)
    # device bf16 -> host bf16 is the same bits
    np.testing.assert_array_equal(k_host, k.astype(jnp.bfloat16))
    assert te.stats.bytes_out == 2 * k_host.nbytes  # 2-byte accounting
    te.join([te.swap_in(req)])
    k_dev, _ = pool.device.read_pages(req.pages)
    np.testing.assert_array_equal(k_dev, k.astype(jnp.bfloat16))
    te.close()

    model = get_model(cfg)
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(1, 500, size=n))) for n in (9, 22, 30)]
    outs = {}
    for pipe in (True, False):
        eng = NeoEngine(cfg, EngineConfig(device_pool_pages=7, host_pool_pages=96,
                                          max_batch_tokens=64, policy="fastdecode",
                                          pipeline=pipe), params=params)
        rids = [eng.submit(p, 5) for p in prompts]
        res = eng.run_until_done(200)
        outs[pipe] = [res[r] for r in rids]
        assert eng.stats.offloaded_decodes > 0
        eng.close()
    assert outs[True] == outs[False]


def test_serial_mode_plans_stay_serial(dense_setup):
    """policy="simple" (strawman #1) must not pipeline even when the engine
    default enables it — its plans are mode="serial" by construction."""
    cfg, model, params = dense_setup
    rng = np.random.default_rng(9)
    p = list(map(int, rng.integers(1, 500, size=12)))
    oracle = _oracle(model, params, p, 5)
    eng = NeoEngine(cfg, EngineConfig(device_pool_pages=8, host_pool_pages=64,
                                      max_batch_tokens=64, policy="simple"),
                    params=params)
    rid = eng.submit(p, 5)
    out = eng.run_until_done(100)
    assert out[rid] == oracle
    assert eng.stats.pipelined_steps == 0
    eng.close()


# ---------------------------------------------------------------------------
# starvation-limit preemption drains a full host pool
# ---------------------------------------------------------------------------


def test_starvation_preemption_drains_full_host_pool(dense_setup):
    """Host requests that cannot allocate their next page are skipped; after
    ``starvation_limit`` skips they are recompute-preempted so the host pool
    drains instead of deadlocking."""
    cfg, _, _ = dense_setup
    ecfg = EngineConfig(device_pool_pages=4, host_pool_pages=4,
                        max_batch_tokens=256, starvation_limit=3, policy="neo")
    perf = PerfModel.for_arch(cfg, ecfg.hw_profile)
    sched = NeoScheduler(cfg, ecfg, perf)
    page = cfg.kv_block_size
    # two host-resident requests pinning 2 pages each (host pool FULL), both
    # exactly at a page boundary so the next token needs a new page
    reqs = []
    for rid in range(2):
        r = Request(rid=rid, prompt=list(range(2 * page)), max_new_tokens=8)
        r.state = RequestState.RUNNING
        r.location = "cpu"
        r.pages = [2 * rid, 2 * rid + 1]
        r.out_tokens = [1]  # kv_len == 2*page -> next token needs page 3
        sched.cpu_runq.append(r)
        reqs.append(r)

    preempted = False
    for _ in range(ecfg.starvation_limit + 1):
        view = PoolView(page_size=page, device_free=0, host_free=0,
                        device_total=4, host_total=4)
        plan = sched.plan(view)
        if plan.preempt:
            preempted = True
            victim = plan.preempt[0]
            survivor = next(r for r in reqs if r is not victim)
            # the victim's pages drained back into the pool — enough for the
            # surviving host request to allocate its next page and decode
            assert survivor in plan.host_rows  # cpu0 or cpu1 sub-batch
            assert view.host_free == len(victim.pages) - 1
            break
    assert preempted, "full host pool never drained via starvation preemption"


def test_full_offload_budget_uses_prefill_len(dense_setup):
    """_plan_full_offload must decrement the token budget by prefill_len —
    the same quantity the admission check used (replayed prefills differ
    from prompt_len)."""
    cfg, _, _ = dense_setup
    ecfg = EngineConfig(device_pool_pages=64, host_pool_pages=64,
                        max_batch_tokens=40, policy="fastdecode")
    perf = PerfModel.for_arch(cfg, ecfg.hw_profile)
    sched = NeoScheduler(cfg, ecfg, perf)
    # a replayed request: long prompt, several emitted tokens -> prefill_len
    # = prompt + emitted - 1 > prompt_len
    r1 = Request(rid=0, prompt=list(range(20)), max_new_tokens=16)
    r1.out_tokens = [1, 2, 3, 4, 5]  # prefill_len = 24 (prompt_len = 20)
    r2 = Request(rid=1, prompt=list(range(18)), max_new_tokens=4)
    sched.add_request(r1)
    sched.add_request(r2)
    view = PoolView(page_size=cfg.kv_block_size, device_free=64, host_free=64,
                    device_total=64, host_total=64)
    plan = sched.plan(view)
    # r1 consumes prefill_len=24 of the 40-token budget, leaving 16 — too
    # small for r2 (prefill_len 18).  The old prompt_len decrement (20) would
    # have admitted r2 and overflowed the activation budget.
    assert r1 in plan.prefill
    assert r2 not in plan.prefill
