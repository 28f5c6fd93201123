"""Plan-ahead scheduling + online-serving tests.

The load-bearing invariant: greedy per-row compute is row-independent and
padding-invariant, so outputs must be BITWISE IDENTICAL whether the plan was
built speculatively (against a predicted post-step view, possibly with stale
EWMA scales) or freshly on the critical path.  Plans may differ; outputs may
not.  A stale speculative plan only ever costs performance (a replan), never
correctness.
"""

import jax
import numpy as np
import pytest

from repro.config import EngineConfig
from repro.configs import get_smoke_config
from repro.core.engine import NeoEngine
from repro.core.request import RequestState
from repro.launch.serve import run_online, run_trace
from repro.models.api import get_model
from repro.serving.metrics import RequestRecord, ServeMetrics
from repro.serving.traces import get_trace, replay_trace, save_trace, synthetic_trace


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen3-0.6b")
    model = get_model(cfg)
    params = model.init(jax.random.key(7))
    return cfg, params


def _make(cfg, params, *, policy="neo", planahead=True, device=7, host=96,
          max_batch_tokens=64, **kw):
    ecfg = EngineConfig(device_pool_pages=device, host_pool_pages=host,
                        max_batch_tokens=max_batch_tokens, policy=policy,
                        planahead=planahead, **kw)
    return NeoEngine(cfg, ecfg, params=params)


def _prompts(rng, sizes):
    return [list(map(int, rng.integers(1, 500, size=n))) for n in sizes]


# ---------------------------------------------------------------------------
# S3: bitwise identity — plan-ahead vs lockstep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["neo", "gpu_only", "fastdecode"])
def test_planahead_bitwise_vs_lockstep(policy, setup, rng):
    """Same prompts, planahead on vs off: identical outputs, and the
    speculative path must actually fire (hits > 0).  The tight device pool
    drives offload/swap traffic for the neo policy, so speculation runs
    against a moving pool — exactly the hard case."""
    cfg, params = setup
    prompts = _prompts(rng, (7, 19, 26, 12))

    outs = {}
    stats = {}
    for planahead in (True, False):
        eng = _make(cfg, params, policy=policy, planahead=planahead)
        rids = [eng.submit(p, 8) for p in prompts]
        done = eng.run_until_done(300)
        outs[planahead] = [done[r] for r in rids]
        stats[planahead] = eng.stats
        eng.close()

    assert outs[True] == outs[False], f"{policy}: plan-ahead changed outputs"
    assert stats[True].planahead_hits > 0, f"{policy}: speculation never adopted"
    assert stats[False].planahead_hits == 0
    assert stats[True].planahead_hidden_time >= 0.0


def test_planahead_forced_replan_on_arrival(setup, rng):
    """An arrival between plan-ahead launch and the next step invalidates
    the speculative plan: replans must increment and outputs stay correct
    (the mid-flight joiner is continuous batching's core move)."""
    cfg, params = setup
    prompts = _prompts(rng, (9, 14))
    late = _prompts(rng, (11,))[0]

    # reference: everything known up front, plan-ahead off
    ref = _make(cfg, params, planahead=False)
    r0, r1 = (ref.submit(p, 8) for p in prompts)
    r2 = ref.submit(late, 8)
    ref_out = ref.run_until_done(300)
    ref.close()

    eng = _make(cfg, params, planahead=True)
    a, b = (eng.submit(p, 8) for p in prompts)
    # step until a speculative plan is in flight, then inject the arrival
    for _ in range(50):
        eng.step()
        if eng._spec is not None:
            break
    assert eng._spec is not None, "speculation never launched"
    c = eng.submit(late, 8)
    before = eng.stats.planahead_replans
    eng.step()  # stale signature: the waitq grew behind the planner's back
    assert eng.stats.planahead_replans == before + 1
    out = eng.run_until_done(300)
    eng.close()

    assert out[a] == ref_out[r0]
    assert out[b] == ref_out[r1]
    assert out[c] == ref_out[r2]


def test_planahead_eos_finish_replans_not_corrupts(setup, rng):
    """An eos stop is deliberately NOT predicted (the planner can't know the
    argmax) — the finish falsifies the signature, forcing a replan, and the
    output still truncates exactly at eos."""
    cfg, params = setup
    p = _prompts(rng, (9,))[0]
    probe = _make(cfg, params, planahead=False, device=16, host=16)
    rid = probe.submit(p, 6)
    seq = probe.run_until_done(100)[rid]
    probe.close()
    # the first token from the third on that the run has not emitted before:
    # the stop lands mid-run (the session-scoped rng fixture can make seq[2]
    # repeat seq[0])
    k = next(i for i in range(2, len(seq)) if seq[i] not in seq[:i])
    eos = seq[k]

    eng = _make(cfg, params, planahead=True, device=16, host=16)
    rid = eng.submit(p, 6, eos_token=eos)
    out = eng.run_until_done(100)
    eng.close()
    assert out[rid] == seq[:k + 1]


# ---------------------------------------------------------------------------
# Continuous batching: admission control, cancellation, open-loop runner
# ---------------------------------------------------------------------------

def test_offer_admission_control(setup, rng):
    cfg, params = setup
    eng = _make(cfg, params, max_waiting=1, device=16, host=32)
    p = _prompts(rng, (6, 6, 6))
    first = eng.offer(p[0], 4)
    assert first is not None
    assert eng.offer(p[1], 4) is None  # waitq full
    assert eng.offer(p[2], 4) is None
    assert eng.stats.rejected_requests == 2
    out = eng.run_until_done(100)
    eng.close()
    assert len(out[first]) == 4


def test_cancel_frees_pages_mid_flight(setup, rng):
    cfg, params = setup
    eng = _make(cfg, params, device=16, host=32)
    keep = eng.submit(_prompts(rng, (8,))[0], 8)
    victim = eng.submit(_prompts(rng, (8,))[0], 8)
    free0 = eng.pool.device.free_pages + eng.pool.host.free_pages
    eng.step()
    eng.step()
    assert eng.cancel(victim)
    assert eng.requests[victim].state == RequestState.ABORTED
    assert not eng.requests[victim].pages
    out = eng.run_until_done(200)
    eng.close()
    assert len(out[keep]) == 8
    # every page the pair held must be back in the pools
    assert eng.pool.device.free_pages + eng.pool.host.free_pages == free0


def test_run_online_streams_and_finishes(setup, rng):
    """Open-loop runner: mid-flight joins, streaming departure, per-request
    TTFT/TPOT recorded, streamed tokens == final out_tokens."""
    cfg, params = setup
    eng = _make(cfg, params, device=24, host=96, max_batch_tokens=256)
    trace = synthetic_trace(6, 50.0, 12, 6, seed=3)
    streamed = {}
    m = run_online(eng, trace, vocab=500, seed=3,
                   on_token=lambda rid, t: streamed.setdefault(rid, []).append(t))
    finals = {rid: list(r.out_tokens) for rid, r in eng.requests.items()}
    eng.close()
    assert len(m.finished) == 6
    assert streamed == finals
    assert m.planahead_hits > 0
    s = m.summary()  # prefill's computed tokens and the graph slots they took
    assert 0 < s["prefill_tokens_computed"] <= s["prefill_slot_tokens"]
    for rec in m.finished:
        assert rec.ttft is not None and rec.ttft >= 0
        assert rec.tpot is None or rec.tpot > 0
    assert np.isfinite(m.ttft(99)) and np.isfinite(m.tpot(50))


def test_trace_replay_roundtrip(tmp_path, rng):
    trace = get_trace("osc", 5, 4.0, seed=1)
    path = str(tmp_path / "t.jsonl")
    save_trace(trace, path)
    back = replay_trace(path)
    assert [(r.arrival_time, r.prompt_len, r.output_len) for r in back] == \
           [(r.arrival_time, r.prompt_len, r.output_len) for r in trace]
    halved = replay_trace(path, 3, time_scale=0.5)
    assert len(halved) == 3
    assert halved[0].arrival_time == trace[0].arrival_time * 0.5


# ---------------------------------------------------------------------------
# Serving metrics math
# ---------------------------------------------------------------------------

def test_metrics_tpot_and_goodput():
    m = ServeMetrics()
    # req 0: ttft 1s, tpot (5-1)/(5-1)=1s — attains (2, 1.5)
    m.records.append(RequestRecord(0, 0.0, 4, 5, first_token_time=1.0,
                                   finish_time=5.0))
    # req 1: ttft 3s — misses the 2s TTFT SLO
    m.records.append(RequestRecord(1, 0.0, 4, 5, first_token_time=3.0,
                                   finish_time=6.0))
    # req 2: single-token output — no TPOT, TTFT-only attainment
    m.records.append(RequestRecord(2, 1.0, 4, 1, first_token_time=2.0,
                                   finish_time=2.0))
    # req 3: never finished — excluded entirely
    m.records.append(RequestRecord(3, 0.0, 4, 5))
    m.makespan = 10.0

    assert m.records[0].tpot == 1.0
    assert m.records[2].tpot is None
    assert m.slo_attained(2.0, 1.5) == 2
    assert m.goodput(2.0, 1.5) == pytest.approx(0.2)
    assert m.goodput(0.5, 1.5) == 0.0
    assert m.ttft(50) == pytest.approx(np.percentile([1.0, 3.0, 1.0], 50))
    assert m.tpot(99) == pytest.approx(np.percentile([1.0, 0.75], 99))
