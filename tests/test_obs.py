"""Observability layer tests (S3).

Three contracts under test:

1. **Tracing never changes behaviour** — greedy outputs are bitwise
   identical tracing on vs off across the pipelined, plan-ahead, and
   prefix-cache paths (every emit site is a pure observer behind an
   ``if tracer is not None`` guard).
2. **The timeline is well-formed** — within any one track, spans nest or
   are disjoint (single-writer-per-track design), the ring drops OLDEST
   events (counted, never blocking), and both sinks round-trip.
3. **The spans carry the truth** — :func:`repro.obs.reconcile.reconcile`
   recomputes lane busy / overlap / bubble / swap-hidden / plan-ahead
   accounting from spans alone and must agree with ``EngineStats``.

Plus the S1/S2 ServeMetrics hardening: NaN-free JSON summaries with zero
finished requests, and terminal-state records for rejected/cancelled
requests.
"""

import json

import jax
import numpy as np
import pytest

from repro.config import EngineConfig
from repro.configs import get_smoke_config
from repro.core.engine import EngineStats, NeoEngine
from repro.models.api import get_model
from repro.obs.reconcile import reconcile
from repro.obs.tracer import SpanTracer
from repro.serving.metrics import RequestRecord, ServeMetrics


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen3-0.6b")
    model = get_model(cfg)
    params = model.init(jax.random.key(7))
    return cfg, params


def _make(cfg, params, *, tracing, policy="neo", device=7, host=96,
          max_batch_tokens=64, **kw):
    ecfg = EngineConfig(device_pool_pages=device, host_pool_pages=host,
                       max_batch_tokens=max_batch_tokens, policy=policy,
                       tracing=tracing, **kw)
    return NeoEngine(cfg, ecfg, params=params)


def _prompts(rng, sizes):
    return [list(map(int, rng.integers(1, 500, size=n))) for n in sizes]


# ---------------------------------------------------------------------------
# ring buffer semantics (pure tracer, no engine)
# ---------------------------------------------------------------------------

def test_ring_overflow_drops_oldest_never_blocks():
    tr = SpanTracer(capacity=8)
    for i in range(20):
        tr.emit("t", f"s{i}", float(i), float(i) + 0.5)
    assert tr.total == 20
    assert tr.dropped == 12
    evs = tr.events()
    assert len(evs) == 8
    # survivors are the NEWEST 8, oldest-first
    assert [e.name for e in evs] == [f"s{i}" for i in range(12, 20)]


def test_ring_no_overflow_keeps_order():
    tr = SpanTracer(capacity=16)
    for i in range(5):
        tr.emit("t", f"s{i}", float(i), float(i) + 0.5)
    assert tr.dropped == 0
    assert [e.name for e in tr.events()] == [f"s{i}" for i in range(5)]


def test_sync_records_the_clock_anchor():
    """sync() brackets a profiler annotation with two perf_counter readings
    (no trace needs to be running)."""
    tr = SpanTracer()
    tr.sync()
    (e,) = tr.events()
    assert (e.ph, e.track, e.name, e.args) == ("i", "engine", "sync", {"name": "obs.sync"})
    assert 0 <= e.t1 - e.t0 < 0.5


def test_reconcile_refuses_wrapped_ring():
    tr = SpanTracer(capacity=2)
    for i in range(5):
        tr.emit("t", "s", float(i), float(i) + 0.5)
    rep = reconcile(tr, EngineStats())
    assert not rep.ok
    assert rep.dropped == 3
    assert rep.notes  # explains the refusal


# ---------------------------------------------------------------------------
# sinks: Chrome trace-event JSON + counters JSONL
# ---------------------------------------------------------------------------

def test_export_chrome_shape(tmp_path):
    tr = SpanTracer()
    tr.emit("engine", "step", 1.0, 2.0, {"iter": 0})
    tr.emit("host0", "lane", 1.2, 1.8, {"iter": 0})
    tr.instant("engine", "plan_adopt", {"dur": 0.01})
    tr.counter("queues", {"waiting": 3, "running": 2})
    tr.async_begin(7, "req", t=1.0, args={"prompt_len": 4})
    tr.async_end(7, "req", t=2.0, args={"outcome": "finished"})
    path = str(tmp_path / "trace.json")
    doc = tr.export_chrome(path)
    on_disk = json.load(open(path))
    assert on_disk == doc

    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert {"engine", "host0"} <= names
    assert any(e["name"] == "process_name" for e in meta)

    spans = [e for e in evs if e["ph"] == "X"]
    assert all("ts" in e and "dur" in e for e in spans)
    # microseconds after the trace's first stamp, on a whole-ns grid
    assert doc["otherData"]["t0_perf_counter_s"] == 1.0
    step = next(e for e in spans if e["name"] == "step")
    assert step["ts"] == 0.0
    assert step["dur"] == 1.0 * 1e6
    lane = next(e for e in spans if e["name"] == "lane")
    assert lane["ts"] == pytest.approx(0.2 * 1e6) and lane["dur"] == pytest.approx(0.6 * 1e6)

    counters = [e for e in evs if e["ph"] == "C"]
    assert counters and counters[0]["args"] == {"waiting": 3, "running": 2}
    asyncs = [e for e in evs if e["ph"] in ("b", "e")]
    assert {a["id"] for a in asyncs} == {"7"}
    assert doc["otherData"]["events_dropped"] == 0


def test_export_counters_jsonl(tmp_path):
    tr = SpanTracer()
    tr.counter("queues", {"waiting": 1}, t=0.5)
    tr.counter("pool_free", {"device": 9, "host": 2}, t=0.6)
    tr.emit("engine", "step", 0.0, 1.0)  # not a counter: excluded
    path = str(tmp_path / "c.jsonl")
    n = tr.export_counters_jsonl(path)
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert n == 2 and len(lines) == 2
    assert lines[0] == {"t": 0.5, "name": "queues", "values": {"waiting": 1}}
    assert lines[1]["values"] == {"device": 9, "host": 2}


# ---------------------------------------------------------------------------
# tracing on vs off: bitwise-identical outputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,kw", [
    ("neo", {}),                      # pipelined swaps, tight device pool
    ("fastdecode", {}),               # host decode lanes
    ("neo", {"planahead": True}),     # speculative planning
])
def test_tracing_bitwise_identity(policy, kw, setup, rng):
    cfg, params = setup
    prompts = _prompts(rng, (7, 19, 26, 12))
    outs = {}
    slots = {}
    for tracing in (False, True):
        eng = _make(cfg, params, tracing=tracing, policy=policy, **kw)
        rids = [eng.submit(p, 8) for p in prompts]
        done = eng.run_until_done(300)
        outs[tracing] = [done[r] for r in rids]
        st = eng.stats
        slots[tracing] = (st.prefill_tokens, st.prefill_slot_tokens)
        assert 0 < st.prefill_tokens <= st.prefill_slot_tokens
        if tracing:
            assert eng.tracer is not None and eng.tracer.total > 0
            _assert_engine_spans(eng.tracer, prompts)
        else:
            assert eng.tracer is None
        eng.close()
    assert outs[True] == outs[False], f"{policy}: tracing changed outputs"
    assert slots[True] == slots[False]


def _assert_engine_spans(tracer, prompts):
    """The spans inside the engine: prefill's steps on the ``prefill`` track
    (one ``prefill.place`` per request, with its rid), the decode logits
    fetch, and a ``swap.join`` per join; every one carries ``iter``."""
    spans = [e for e in tracer.events() if e.ph == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e.name, []).append(e)
    for name in ("prefill.graph", "prefill.place", "prefill.logits"):
        assert by_name[name] and {e.track for e in by_name[name]} == {"prefill"}
    place = by_name["prefill.place"]
    assert {e.args["rid"] for e in place} >= set(range(len(prompts)))
    assert all(e.args["pages"] > 0 and e.args["bytes"] > 0 and "to_host" in e.args
               for e in place)
    assert {e.track for e in by_name.get("decode.logits", [])} <= {"decode"}
    assert by_name["swap.join"]
    for e in spans:
        if e.name.startswith(("prefill.", "decode.", "swap.")):
            assert "iter" in e.args, e
    names = {e.name for e in tracer.events()}
    assert not names & {"tok", "plan_skip", "drain", "join_out"}


def test_tracing_bitwise_identity_prefix_cache(setup, rng):
    cfg, params = setup
    shared = list(map(int, rng.integers(1, 500, size=40)))
    prompts = [shared + list(map(int, rng.integers(1, 500, size=12)))
               for _ in range(3)]
    outs = {}
    for tracing in (False, True):
        eng = _make(cfg, params, tracing=tracing, device=64, host=128,
                    max_batch_tokens=512, prefix_cache=True)
        out = {}
        for p in prompts:  # sequential: earlier requests seed the tree
            eng.submit(p, 6)
            out.update(eng.run_until_done(300))
        assert eng.prefix_cache.stats.hits > 0
        outs[tracing] = out
        eng.close()
    assert outs[True] == outs[False], "tracing changed prefix-cache outputs"


# ---------------------------------------------------------------------------
# span well-formedness: per-track spans nest or are disjoint
# ---------------------------------------------------------------------------

def _assert_well_formed(tracer):
    by_track = {}
    for e in tracer.events():
        if e.ph == "X":
            assert e.t1 >= e.t0, f"negative span {e.track}/{e.name}"
            by_track.setdefault(e.track, []).append(e)
    assert by_track, "no spans recorded"
    for track, evs in by_track.items():
        # enclosing-first order; a stack then proves nest-or-disjoint
        evs.sort(key=lambda e: (e.t0, -e.t1))
        stack = []
        for e in evs:
            while stack and stack[-1].t1 <= e.t0:
                stack.pop()
            if stack:
                assert e.t1 <= stack[-1].t1, (
                    f"{track}: {e.name} [{e.t0},{e.t1}] straddles "
                    f"{stack[-1].name} [{stack[-1].t0},{stack[-1].t1}]")
            stack.append(e)
    return by_track


def test_span_well_formedness_and_coverage(setup, rng):
    """One traced mixed run: every track's spans nest-or-disjoint, and the
    tracks the instrumentation promises actually show up."""
    cfg, params = setup
    eng = _make(cfg, params, tracing=True, policy="fastdecode",
                device=48, host=256, max_batch_tokens=256, planahead=True)
    for p in _prompts(rng, (7, 19, 26, 12, 9, 15)):
        eng.submit(p, 8)
    eng.run_until_done(400)
    tracer, stats = eng.tracer, eng.stats
    eng.close()

    by_track = _assert_well_formed(tracer)
    assert "engine" in by_track
    assert any(t.startswith("host") and not t.startswith("hostattn")
               for t in by_track), "no host lane spans on a fastdecode run"
    assert any(t.startswith("hostattn") for t in by_track)
    assert "sched" in by_track
    # every step span carries its iteration id
    steps = [e for e in by_track["engine"] if e.name == "step"]
    assert len(steps) == stats.iterations
    # request lifecycle: a begin and an end per submitted request
    begins = [e for e in tracer.events() if e.ph == "b" and e.name == "req"]
    ends = [e for e in tracer.events() if e.ph == "e" and e.name == "req"]
    assert len(begins) == 6 and len(ends) == 6


# ---------------------------------------------------------------------------
# reconcile(): spans must reproduce EngineStats
# ---------------------------------------------------------------------------

def _reconcile_run(cfg, params, rng, **kw):
    eng = _make(cfg, params, tracing=True, **kw)
    for p in _prompts(rng, (7, 19, 26, 12)):
        eng.submit(p, 8)
    eng.run_until_done(400)
    rep = reconcile(eng.tracer, eng.stats)
    eng.close()
    assert rep.ok, f"reconcile failed: {rep.failed()}\n{rep.summary()}"
    return rep


def test_reconcile_fastdecode(setup, rng):
    cfg, params = setup
    rep = _reconcile_run(cfg, params, rng, policy="fastdecode",
                         device=48, host=256, max_batch_tokens=256)
    assert any(k.startswith("lane_busy[host") for k in rep.checks)


def test_reconcile_mixed_neo_tight_pool(setup, rng):
    """Tight device pool: swaps + mixed plans — the swap_hidden_bytes and
    overlap formulas get exercised with real copy traffic."""
    cfg, params = setup
    rep = _reconcile_run(cfg, params, rng, policy="neo", planahead=True)
    assert "swap_hidden_bytes" in rep.checks
    assert "bubble_fraction" in rep.checks
    # swap.join spans (engine thread and lane threads) sum to the join wait
    assert rep.checks["swap_wait_time"]["stats"] > 0


def test_reconcile_planahead_adoptions(setup, rng):
    cfg, params = setup
    eng = _make(cfg, params, tracing=True, policy="neo", planahead=True)
    for p in _prompts(rng, (7, 19, 26, 12)):
        eng.submit(p, 8)
    eng.run_until_done(400)
    rep = reconcile(eng.tracer, eng.stats)
    adopted = [e for e in eng.tracer.events()
               if e.ph == "i" and e.name == "plan_adopt"]
    hits = eng.stats.planahead_hits
    eng.close()
    assert rep.ok, f"reconcile failed: {rep.failed()}"
    assert hits > 0 and len(adopted) == hits


# ---------------------------------------------------------------------------
# request lifecycle terminal events (reject / cancel)
# ---------------------------------------------------------------------------

def test_trace_reject_and_cancel_events(setup, rng):
    cfg, params = setup
    eng = _make(cfg, params, tracing=True, device=16, host=32, max_waiting=1)
    p = _prompts(rng, (6, 6, 6))
    first = eng.offer(p[0], 4)
    assert first is not None
    assert eng.offer(p[1], 4) is None
    victim = eng.submit(p[2], 8)
    eng.step()
    assert eng.cancel(victim)
    eng.run_until_done(100)
    evs = eng.tracer.events()
    eng.close()
    rejects = [e for e in evs if e.ph == "i" and e.name == "reject"]
    assert len(rejects) == 1 and rejects[0].args["reason"] == "max_waiting"
    ends = {e.rid: e.args["outcome"] for e in evs
            if e.ph == "e" and e.name == "req"}
    assert ends[victim] == "cancelled"
    assert ends[first] == "finished"


# ---------------------------------------------------------------------------
# S1: NaN-free JSON summary with zero finished requests
# ---------------------------------------------------------------------------

def test_summary_json_safe_zero_finished():
    m = ServeMetrics()
    s = m.summary()
    # allow_nan=False raises on nan/inf: the summary must be strictly valid
    json.dumps(s, allow_nan=False)
    assert s["requests"] == 0
    assert s["per_token_latency_ms"] is None
    assert s["ttft_p99_ms"] is None
    assert s["tpot_p50_ms"] is None
    assert s["throughput_tok_s"] == 0.0


def test_summary_json_safe_only_rejections():
    m = ServeMetrics()
    m.record_rejection(0.5, 10, 4)
    m.makespan = 1.0
    s = m.summary()
    json.dumps(s, allow_nan=False)
    assert s["terminal_counts"]["rejected"] == 1
    assert s["requests"] == 0


# ---------------------------------------------------------------------------
# S2: terminal state for non-finished requests
# ---------------------------------------------------------------------------

def test_terminal_counts_partition():
    m = ServeMetrics()
    m.records.append(RequestRecord(0, 0.0, 4, 5, first_token_time=1.0,
                                   finish_time=5.0, status="finished"))
    m.records.append(RequestRecord(1, 0.0, 4, 5))  # still active
    m.record_rejection(0.2, 8, 4, "max_waiting")
    m.record_rejection(0.3, 8, 4, "max_waiting")
    m.records.append(RequestRecord(4, 0.0, 4, 5))
    assert m.record_cancelled(4, finish_time=2.0)
    assert not m.record_cancelled(99)

    tc = m.terminal_counts
    assert tc == {"finished": 1, "active": 1, "rejected": 2, "cancelled": 1}
    assert sum(tc.values()) == len(m.records)
    assert m.reject_reasons == {"max_waiting": 2}
    # cancelled records keep a departure time but never count as finished
    assert [r.rid for r in m.finished] == [0]
    assert m.records[-1].finish_time == 2.0


def test_cancelled_excluded_from_latency_stats():
    m = ServeMetrics()
    m.records.append(RequestRecord(0, 0.0, 4, 4, first_token_time=1.0,
                                   finish_time=3.0))
    m.records.append(RequestRecord(1, 0.0, 4, 4, first_token_time=0.5,
                                   finish_time=900.0))
    m.record_cancelled(1)
    m.makespan = 10.0
    assert m.total_output_tokens == 4  # only the finished one
    assert np.isfinite(m.ttft())
    assert m.ttft() == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# validate: single-writer well-formedness incl. per-shard TP tracks (PR-8
# open item: the hostattn-*-s<N> rows must get the same nest-or-disjoint
# check as the unsharded tracks)
# ---------------------------------------------------------------------------


def _chrome_doc(extra_tracks):
    """A minimal valid trace doc: device + planner rows, one request
    lifecycle, plus ``extra_tracks`` as {name: [(ts, dur, name), ...]}."""
    tracks = {"device": [(0, 10, "decode")],
              "planner": [(0, 2, "plan")]}
    tracks.update(extra_tracks)
    evs = []
    for tid, (track, spans) in enumerate(sorted(tracks.items()), start=1):
        evs.append({"ph": "M", "name": "thread_name", "pid": 1, "tid": tid,
                    "args": {"name": track}})
        for ts, dur, name in spans:
            evs.append({"ph": "X", "pid": 1, "tid": tid, "ts": ts,
                        "dur": dur, "name": name, "args": {}})
    evs.append({"ph": "b", "cat": "req", "name": "req", "id": 1, "pid": 1,
                "tid": 1, "ts": 0})
    evs.append({"ph": "e", "cat": "req", "name": "req", "id": 1, "pid": 1,
                "tid": 1, "ts": 10})
    return {"traceEvents": evs, "otherData": {"events_dropped": 0}}


def _validate_doc(tmp_path, doc):
    from repro.obs.validate import validate
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(doc))
    return validate(str(p))


def test_validate_accepts_wellformed_tp2_shard_tracks(tmp_path):
    # TP=2 fixture: each shard callback owns its own hostattn row; spans
    # within a row nest or are disjoint
    doc = _chrome_doc({
        "hostattn-b0-s0": [(0, 4, "layer0"), (1, 2, "attend"), (5, 3, "layer1")],
        "hostattn-b0-s1": [(0, 4, "layer0"), (5, 3, "layer1")],
        "hostattn-prefix-s0": [(20, 2, "prefix")],
        "hostattn-prefix-s1": [(20, 2, "prefix")],
    })
    assert _validate_doc(tmp_path, doc) == []


def test_validate_flags_two_writers_on_one_shard_track(tmp_path):
    # the regression validate must catch: two shard callbacks emitting onto
    # ONE per-shard row — overlapping spans that do not nest
    doc = _chrome_doc({
        "hostattn-b0-s0": [(0, 5, "layer0"), (3, 6, "layer0")],
        "hostattn-b0-s1": [(0, 4, "layer0")],
    })
    fails = _validate_doc(tmp_path, doc)
    assert any("hostattn-b0-s0" in f and "single-writer" in f for f in fails)


def test_validate_flags_overlap_on_unsharded_track_too(tmp_path):
    doc = _chrome_doc({"copy-out": [(0, 5, "out"), (4, 4, "out")]})
    fails = _validate_doc(tmp_path, doc)
    assert any("copy-out" in f and "single-writer" in f for f in fails)


def test_export_nests_shared_end_stamps(tmp_path):
    """Spans that share a perf_counter stamp nest in the export: thousands of
    lane/compute pairs sharing their end at perf_counter magnitudes (1e5 s),
    where a float microsecond ``ts + dur`` rounds one ulp apart; a child that
    ends one nanosecond past its parent is still flagged."""
    gen = np.random.default_rng(13)
    tr = SpanTracer(capacity=1 << 14)
    t = 1.0e5
    for _ in range(4000):
        t0 = t + gen.uniform(0.0, 1e-3)
        t1 = t0 + gen.uniform(1e-6, 1e-2)
        tr.emit("host0", "lane", t0, t1)
        tr.emit("host0", "compute", t0 + gen.uniform(0.0, t1 - t0), t1)
        t = t1
    tr.emit("host1", "lane", t, t + 1e-3)
    tr.emit("host1", "compute", t + 5e-4, t + 1e-3 + 1e-9)
    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    from repro.obs.validate import validate
    bad = [f for f in validate(str(path)) if "single-writer" in f]
    assert len(bad) == 1 and "'host1'" in bad[0], bad


def test_validate_real_tp2_export_passes(tmp_path):
    """End-to-end TP=2 fixture: a traced TP=2 serve on a fake-device mesh
    exports per-shard hostattn tracks, and the export passes validate's
    single-writer check (subprocess: needs XLA fake host devices)."""
    from tests.conftest import run_subprocess

    out = run_subprocess("""
import json
import os
import tempfile
import numpy as np
from repro.config import EngineConfig
from repro.configs import get_smoke_config
from repro.core.engine import NeoEngine
from repro.core.request import RequestState
from repro.obs.tracer import SpanTracer
from repro.obs.validate import validate

cfg = get_smoke_config('qwen3-0.6b')
ecfg = EngineConfig(device_pool_pages=10, host_pool_pages=64,
                    max_batch_tokens=1024, policy='neo', tp=2)
eng = NeoEngine(cfg, ecfg)
tracer = SpanTracer()
eng.attach_tracer(tracer)
rng = np.random.default_rng(0)
rids = [eng.submit(rng.integers(0, cfg.vocab_size, size=24 + 3 * i).tolist(), 6)
        for i in range(4)]
for _ in range(300):
    eng.step()
    if all(eng.requests[r].state == RequestState.FINISHED for r in rids):
        break
eng.close()
path = os.path.join(tempfile.mkdtemp(), 'trace_tp2_test.json')
doc = tracer.export_chrome(path)
tracks = sorted({e['args']['name'] for e in doc['traceEvents']
                 if e.get('ph') == 'M' and e.get('name') == 'thread_name'})
fails = validate(path)
print(json.dumps({'tracks': tracks, 'fails': fails}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["fails"] == []
    shard_tracks = [t for t in res["tracks"]
                    if t.startswith("hostattn") and t.endswith(("-s0", "-s1"))]
    assert shard_tracks, f"no per-shard hostattn tracks in {res['tracks']}"
