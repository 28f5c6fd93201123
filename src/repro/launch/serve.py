"""Serving launcher.

Runs the real NeoEngine on this host (smoke/mini configs execute end-to-end;
full configs are exercised via the dry-run).  Two loops:

* :func:`run_trace` — the closed-loop runner the offline gates use: requests
  are submitted directly as their arrival time passes and the plan is built
  on the critical path when plan-ahead is off.
* :func:`run_online` — open-loop continuous batching: requests are OFFERED
  (admission control may reject), join the running batch mid-flight, and
  stream out the moment they finish; plan-ahead builds iteration N+1's plan
  while iteration N's lanes execute.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --trace osc --n 24 --rate 8 --policy neo --arrivals poisson

The ``--sustained`` flag runs the A/B gate (closed-loop lockstep vs
open-loop + plan-ahead) used by CI and bench_trend.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.config import EngineConfig
from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.core.engine import NeoEngine
from repro.core.request import RequestState
from repro.launch.compile_cache import setup_compile_cache
from repro.obs.reconcile import reconcile
from repro.serving.metrics import RequestRecord, ServeMetrics
from repro.serving.traces import get_trace, save_trace


def _mirror_stats(engine: NeoEngine, metrics: ServeMetrics) -> None:
    """Copy EngineStats / prefix-cache counters into a ServeMetrics."""
    metrics.iterations = engine.stats.iterations
    metrics.mode_counts = dict(engine.stats.mode_counts)
    metrics.offloaded_decodes = engine.stats.offloaded_decodes
    metrics.device_decodes = engine.stats.device_decodes
    metrics.host_busy_time = engine.stats.host_busy_time
    metrics.device_busy_time = engine.stats.device_busy_time
    metrics.pipeline_overlap_time = engine.stats.pipeline_overlap_time
    metrics.bubble_fraction = engine.stats.bubble_fraction
    metrics.swap_hidden_bytes = engine.stats.swap_hidden_bytes
    metrics.swap_wait_time = engine.stats.swap_wait_time
    metrics.microbatched_steps = engine.stats.microbatched_steps
    metrics.serial_b1_steps = engine.stats.serial_b1_steps
    metrics.borrowed_lane_steps = engine.stats.borrowed_lane_steps
    metrics.lane_count_steps = dict(engine.stats.lane_counts)
    metrics.lane_busy = dict(engine.stats.lane_busy_time)
    metrics.prefill_tokens_computed = engine.stats.prefill_tokens
    metrics.prefill_slot_tokens = engine.stats.prefill_slot_tokens
    metrics.planahead_hits = engine.stats.planahead_hits
    metrics.planahead_replans = engine.stats.planahead_replans
    metrics.planahead_skipped = engine.stats.planahead_skipped
    metrics.plan_busy_time = engine.stats.plan_busy_time
    metrics.planahead_hidden_time = engine.stats.planahead_hidden_time
    metrics.rejected_requests = engine.stats.rejected_requests
    metrics.spec_steps = engine.stats.spec_steps
    metrics.drafted_tokens = engine.stats.drafted_tokens
    metrics.accepted_tokens = engine.stats.accepted_tokens
    metrics.rejected_drafts = engine.stats.rejected_drafts
    metrics.spec_busy_time = engine.stats.spec_busy_time
    metrics.accept_len_hist = dict(engine.stats.accept_len_hist)
    if engine.pool is not None:
        metrics.swap_bytes = engine.pool.swap_bytes
    if getattr(engine, "prefix_cache", None) is not None:
        ps = engine.prefix_cache.stats
        metrics.prefix_hit_rate = ps.hit_rate
        metrics.prefix_hits = ps.hits
        metrics.prefix_lookups = ps.lookups
        metrics.prefix_hit_tokens = ps.hit_tokens
        metrics.prefix_promoted_pages = ps.promoted_pages
        metrics.prefix_demoted_pages = ps.demoted_pages
        metrics.prefix_evicted_pages = ps.evicted_pages
        metrics.prefix_cow_copies = ps.cow_copies
        metrics.inplace_host_hits = ps.inplace_host_hits
        metrics.host_served_hit_tokens = ps.host_served_hit_tokens
        metrics.host_hit_pcie_bytes = ps.host_hit_pcie_bytes


def run_trace(engine: NeoEngine, trace, *, vocab: int, seed: int = 0,
              extras_fn=None, max_iters: int = 100_000) -> ServeMetrics:
    """Feed a trace into a real engine, respecting arrival times (virtual
    clock advanced by wall-time of each iteration)."""
    rng = np.random.default_rng(seed)
    pending = sorted(trace, key=lambda t: t.arrival_time)
    for t in pending:
        t.materialise(rng, vocab)
    metrics = ServeMetrics()
    records = {}
    i = 0
    iters = 0
    t0 = time.perf_counter()
    while iters < max_iters:
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i].arrival_time <= now:
            tr = pending[i]
            extras = extras_fn(tr) if extras_fn else None
            rid = engine.submit(tr.prompt, tr.output_len,
                                arrival_time=tr.arrival_time, extras=extras)
            records[rid] = RequestRecord(rid, tr.arrival_time, tr.prompt_len, tr.output_len)
            metrics.records.append(records[rid])
            i += 1
        emitted = engine.step(now=now)
        iters += 1
        done_now = time.perf_counter() - t0
        for rid, req in engine.requests.items():
            rec = records.get(rid)
            if rec is None:
                continue
            if req.first_token_time is not None and rec.first_token_time is None:
                rec.first_token_time = done_now
            if req.finish_time is not None and rec.finish_time is None:
                rec.finish_time = done_now
                rec.status = ("cancelled"
                              if req.state == RequestState.ABORTED
                              else "finished")
        if not emitted and i >= len(pending) and engine.scheduler.num_queued == 0:
            break
        if not emitted and i < len(pending):
            time.sleep(max(0.0, pending[i].arrival_time - (time.perf_counter() - t0)))
    metrics.makespan = time.perf_counter() - t0
    _mirror_stats(engine, metrics)
    return metrics


def run_online(engine: NeoEngine, trace, *, vocab: int, seed: int = 0,
               extras_fn=None, max_iters: int = 100_000,
               on_token=None) -> ServeMetrics:
    """Open-loop continuous-batching loop.

    Requests are OFFERED as their arrival time passes — admission control
    (``EngineConfig.max_waiting``) may reject them, in which case the client
    gives up and the request counts against goodput.  Admitted requests join
    the running batch mid-flight and depart (stream their final token via
    ``on_token``) the moment they finish, without any generation-round
    barrier.  ``on_token(rid, token)`` is invoked once per newly emitted
    token, in emission order per request.
    """
    rng = np.random.default_rng(seed)
    pending = sorted(trace, key=lambda t: t.arrival_time)
    for t in pending:
        t.materialise(rng, vocab)
    metrics = ServeMetrics()
    records = {}
    streamed = {}  # rid -> tokens already handed to on_token
    i = 0
    iters = 0
    t0 = time.perf_counter()
    while iters < max_iters:
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i].arrival_time <= now:
            tr = pending[i]
            extras = extras_fn(tr) if extras_fn else None
            rid = engine.offer(tr.prompt, tr.output_len,
                               arrival_time=tr.arrival_time, extras=extras)
            i += 1
            if rid is None:
                # rejected at admission; no retry — keep a terminal record
                # so the request ledger still accounts for it
                metrics.record_rejection(tr.arrival_time, tr.prompt_len,
                                         tr.output_len, "max_waiting")
                continue
            records[rid] = RequestRecord(rid, tr.arrival_time, tr.prompt_len,
                                         tr.output_len)
            metrics.records.append(records[rid])
        emitted = engine.step(now=now)
        iters += 1
        done_now = time.perf_counter() - t0
        for rid, req in engine.requests.items():
            rec = records.get(rid)
            if rec is None:
                continue
            if req.first_token_time is not None and rec.first_token_time is None:
                rec.first_token_time = done_now
            if on_token is not None:
                seen = streamed.get(rid, 0)
                for tok in req.out_tokens[seen:]:
                    on_token(rid, tok)
                streamed[rid] = len(req.out_tokens)
            if req.finish_time is not None and rec.finish_time is None:
                rec.finish_time = done_now
                rec.status = ("cancelled"
                              if req.state == RequestState.ABORTED
                              else "finished")
        if not emitted and i >= len(pending) and engine.scheduler.num_queued == 0:
            break
        if not emitted and i < len(pending):
            time.sleep(max(0.0, pending[i].arrival_time - (time.perf_counter() - t0)))
    metrics.makespan = time.perf_counter() - t0
    _mirror_stats(engine, metrics)
    return metrics


def _clamp_trace(trace, max_batch_tokens: int, max_output: int = 32):
    """Clamp lengths to smoke scale (prefix-truncation keeps shared heads
    shared, so multiturn prompts stay cacheable)."""
    for t in trace:
        t.prompt_len = min(t.prompt_len, max_batch_tokens // 4)
        if t.prompt is not None:
            t.prompt = t.prompt[: t.prompt_len]
        t.output_len = min(t.output_len, max_output)
    return trace


def run_sustained(*, arch: str = "qwen3-0.6b", smoke: bool = True,
                  policy: str = "neo", trace_name: str = "osc",
                  n: int = 24, rate: float = 8.0,
                  device_pages: int = 64, host_pages: int = 256,
                  max_batch_tokens: int = 2048,
                  slo_ttft: float = 10.0, slo_tpot: float = 1.0,
                  max_output: int = 16, seed: int = 0,
                  goodput_tol: float = 0.95) -> dict:
    """Sustained-load A/B gate: closed-loop lockstep (plan-ahead OFF, plan
    built on the critical path every step) vs the open-loop arrival-driven
    runner with plan-ahead ON.  Both runs see the same trace, seed, and
    randomly initialised parameters.

    Greedy per-row compute is row-independent and padding-invariant, so the
    two runs must produce **bitwise identical** output tokens per request —
    any divergence is a scheduling bug, not noise.  Gates:

    * ``planahead_hits > 0`` — speculation actually adopted plans,
    * bitwise-identical outputs,
    * open-loop p99 TTFT within the SLO,
    * open-loop goodput >= ``goodput_tol`` x closed-loop goodput.
    """
    cfg = get_smoke_config(arch) if smoke else get_config(arch)

    def build(planahead: bool) -> NeoEngine:
        ecfg = EngineConfig(
            device_pool_pages=device_pages, host_pool_pages=host_pages,
            max_batch_tokens=max_batch_tokens, policy=policy,
            planahead=planahead, seed=seed)
        return NeoEngine(cfg, ecfg)

    def mk_trace():
        return _clamp_trace(get_trace(trace_name, n, rate, seed),
                            max_batch_tokens, max_output)

    def outputs(engine: NeoEngine):
        return {rid: list(r.out_tokens) for rid, r in engine.requests.items()}

    closed = build(planahead=False)
    m_closed = run_trace(closed, mk_trace(), vocab=cfg.vocab_size, seed=seed)
    out_closed = outputs(closed)
    closed.close()

    open_ = build(planahead=True)
    m_open = run_online(open_, mk_trace(), vocab=cfg.vocab_size, seed=seed)
    out_open = outputs(open_)
    open_.close()

    g_closed = m_closed.goodput(slo_ttft, slo_tpot)
    g_open = m_open.goodput(slo_ttft, slo_tpot)
    p99_ttft_open = m_open.ttft(99)
    gates = {
        "planahead_hits_gt0": m_open.planahead_hits > 0,
        "bitwise_identical": out_open == out_closed,
        "p99_ttft_within_slo": bool(p99_ttft_open <= slo_ttft),
        "goodput_no_regress": bool(g_open >= goodput_tol * g_closed),
    }
    return {
        "policy": policy,
        "trace": trace_name,
        "n": n,
        "rate_rps": rate,
        "slo_ttft_s": slo_ttft,
        "slo_tpot_s": slo_tpot,
        "closed": {
            "goodput_rps": round(g_closed, 3),
            "makespan_s": round(m_closed.makespan, 3),
            "ttft_p99_ms": round(m_closed.ttft(99) * 1e3, 2),
            "tpot_p99_ms": round(m_closed.tpot(99) * 1e3, 2),
            "plan_busy_s": round(m_closed.plan_busy_time, 4),
        },
        "open": {
            "goodput_rps": round(g_open, 3),
            "makespan_s": round(m_open.makespan, 3),
            "ttft_p99_ms": round(p99_ttft_open * 1e3, 2),
            "tpot_p99_ms": round(m_open.tpot(99) * 1e3, 2),
            "plan_busy_s": round(m_open.plan_busy_time, 4),
            "planahead_hits": m_open.planahead_hits,
            "planahead_replans": m_open.planahead_replans,
            "planahead_skipped": m_open.planahead_skipped,
            "planahead_hidden_s": round(m_open.planahead_hidden_time, 4),
            "rejected_requests": m_open.rejected_requests,
        },
        "gates": gates,
        "pass": all(gates.values()),
    }


def main(argv=None) -> int:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--policy", default="neo",
                    choices=["neo", "gpu_only", "fastdecode", "simple"])
    ap.add_argument("--trace", default="osc")
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--device-pages", type=int, default=64)
    ap.add_argument("--host-pages", type=int, default=256)
    ap.add_argument("--max-batch-tokens", type=int, default=2048)
    ap.add_argument("--no-pipeline", action="store_true",
                    help="serial reference execution (no async swaps/overlap)")
    ap.add_argument("--no-microbatch", action="store_true",
                    help="disable multi-lane batch-1 splitting (inline "
                         "serial host attention / single classic lane)")
    ap.add_argument("--max-host-lanes", type=int,
                    default=EngineConfig.max_host_lanes,
                    help="upper bound K on concurrent host lanes per plan")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="two-tier radix prefix cache (COW KV page sharing)")
    ap.add_argument("--require-hits", action="store_true",
                    help="exit nonzero if the prefix-cache hit rate is 0 "
                         "(CI smoke gate for shared-prefix traces)")
    ap.add_argument("--host-serving", action="store_true",
                    help="zero-copy host-serving gate: exit nonzero unless "
                         ">= 1 host-resident prefix was pinned in place "
                         "(inplace_host_hits > 0) and host-hit PCIe bytes "
                         "stay within a small epsilon")
    ap.add_argument("--arrivals", default="closed",
                    help="closed = lockstep runner (run_trace); poisson = "
                         "open-loop continuous batching (run_online) with "
                         "the --trace generator's Poisson arrivals; "
                         "replay:<path.jsonl> = open-loop with replayed "
                         "arrival timestamps")
    ap.add_argument("--no-planahead", action="store_true",
                    help="disable speculative plan-ahead (plan on the "
                         "critical path every step)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="admission control: reject offers once this many "
                         "requests are waiting (0 = unbounded)")
    ap.add_argument("--slo-ttft", type=float, default=10.0,
                    help="TTFT SLO in seconds (goodput attainment)")
    ap.add_argument("--slo-tpot", type=float, default=1.0,
                    help="TPOT SLO in seconds/token (goodput attainment)")
    ap.add_argument("--sustained", action="store_true",
                    help="sustained-load A/B gate: closed-loop lockstep vs "
                         "open-loop + plan-ahead; exit nonzero if "
                         "planahead_hits == 0, outputs diverge, p99 TTFT "
                         "misses the SLO, or goodput regresses")
    ap.add_argument("--save-trace", default="",
                    help="write the (clamped) trace as JSONL for replay")
    ap.add_argument("--trace-out", default="",
                    help="enable structured engine tracing and write the "
                         "Chrome trace-event JSON (Perfetto-loadable) here; "
                         "the counter time-series lands next to it as "
                         "<stem>.counters.jsonl unless --counters-out is "
                         "given")
    ap.add_argument("--counters-out", default="",
                    help="JSONL sink for the tracer's counter time-series "
                         "(queue depths, free pages); requires --trace-out")
    ap.add_argument("--require-reconcile", action="store_true",
                    help="exit nonzero unless reconcile() — the span-vs-"
                         "EngineStats accounting audit — passes (implies "
                         "tracing; use with --trace-out)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shard count; column-sharded QKV/"
                         "gate/up with a gather before the replicated O/down "
                         "projections, so greedy outputs stay bitwise "
                         "identical to --tp 1 (needs >= N local devices, "
                         "e.g. XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding: n-gram drafter + chained "
                         "verification on the unchanged fused decode graph; "
                         "greedy outputs stay bitwise identical to "
                         "non-speculative decode (see docs/spec_decode.md)")
    ap.add_argument("--spec-k", type=int, default=EngineConfig.spec_k,
                    help="max drafted tokens per row per step; the perf "
                         "model prices K in [1, spec_k] each plan")
    ap.add_argument("--draft-model", default="",
                    help="arch name of a tiny draft model (e.g. qwen3-0.6b) "
                         "to use instead of the n-gram drafter; implies "
                         "--spec-decode")
    ap.add_argument("--require-accepts", action="store_true",
                    help="exit nonzero if speculative decoding accepted 0 "
                         "drafted tokens (CI smoke gate; use with "
                         "--spec-decode)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.sustained:
        result = run_sustained(
            arch=args.arch, smoke=args.smoke, policy=args.policy,
            trace_name=args.trace, n=args.n, rate=args.rate,
            device_pages=args.device_pages, host_pages=args.host_pages,
            max_batch_tokens=args.max_batch_tokens,
            slo_ttft=args.slo_ttft, slo_tpot=args.slo_tpot,
            seed=args.seed)
        print(json.dumps(result, indent=1))
        if not result["pass"]:
            failed = [k for k, ok in result["gates"].items() if not ok]
            print(f"[serve] FAIL: sustained-load gates failed: {failed}")
            return 1
        print("[serve] sustained-load OK: open-loop + plan-ahead holds "
              "goodput at the SLO with bitwise-identical outputs")
        return 0

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tracing = bool(args.trace_out) or args.require_reconcile
    ecfg = EngineConfig(
        device_pool_pages=args.device_pages,
        host_pool_pages=args.host_pages,
        max_batch_tokens=args.max_batch_tokens,
        policy=args.policy,
        pipeline=not args.no_pipeline,
        microbatch=not args.no_microbatch,
        max_host_lanes=args.max_host_lanes,
        prefix_cache=args.prefix_cache,
        planahead=not args.no_planahead,
        max_waiting=args.max_waiting,
        tracing=tracing,
        tp=args.tp,
        spec_decode=args.spec_decode or bool(args.draft_model),
        spec_k=args.spec_k,
        seed=args.seed,
    )
    open_loop = args.arrivals != "closed"
    print(f"[serve] arch={cfg.name} policy={args.policy} "
          f"pipeline={not args.no_pipeline} "
          f"microbatch={not args.no_microbatch} "
          f"prefix_cache={args.prefix_cache} "
          f"planahead={not args.no_planahead} "
          f"arrivals={args.arrivals} tp={args.tp} "
          f"spec={ecfg.spec_decode} "
          f"pools=({args.device_pages},{args.host_pages})")
    engine = NeoEngine(cfg, ecfg)
    if args.draft_model:
        import jax

        from repro.core.spec import DraftModelDrafter
        from repro.models.api import get_model
        dcfg = (get_smoke_config(args.draft_model) if args.smoke
                else get_config(args.draft_model))
        if dcfg.vocab_size != cfg.vocab_size:
            print(f"[serve] FAIL: draft vocab {dcfg.vocab_size} != target "
                  f"vocab {cfg.vocab_size} (token ids are proposed verbatim)")
            return 1
        dmodel = get_model(dcfg)
        dparams = dmodel.init(jax.random.key(args.seed + 1))
        engine.drafter = DraftModelDrafter(dmodel, dparams,
                                           vocab_size=cfg.vocab_size)
        print(f"[serve] draft model: {dcfg.name} "
              f"(window={engine.drafter.window})")
    if args.arrivals.startswith("replay:"):
        trace = get_trace(args.arrivals, args.n, args.rate, args.seed)
    else:
        trace = get_trace(args.trace, args.n, args.rate, args.seed)
    _clamp_trace(trace, args.max_batch_tokens)
    if args.save_trace:
        save_trace(trace, args.save_trace)
        print(f"[serve] wrote {len(trace)} requests to {args.save_trace}")
    runner = run_online if open_loop else run_trace
    m = runner(engine, trace, vocab=cfg.vocab_size, seed=args.seed)
    engine.close()
    print(json.dumps(m.summary(), indent=1))
    print("scheduler modes:", m.mode_counts)
    if engine.tracer is not None:
        if args.trace_out:
            trace_doc = engine.tracer.export_chrome(args.trace_out)
            print(f"[serve] wrote {len(trace_doc['traceEvents'])} trace "
                  f"events to {args.trace_out} "
                  f"(recorded={engine.tracer.total} "
                  f"dropped={engine.tracer.dropped})")
            counters_out = args.counters_out
            if not counters_out:
                stem = args.trace_out
                if stem.endswith(".json"):
                    stem = stem[: -len(".json")]
                counters_out = stem + ".counters.jsonl"
            n_c = engine.tracer.export_counters_jsonl(counters_out)
            print(f"[serve] wrote {n_c} counter samples to {counters_out}")
        report = reconcile(engine.tracer, engine.stats)
        print(report.summary())
        if args.require_reconcile and not report.ok:
            print("[serve] FAIL: span timeline disagrees with EngineStats")
            return 1
    if args.require_hits and m.prefix_hit_rate <= 0.0:
        print("[serve] FAIL: prefix-cache hit rate is 0 on a shared-prefix trace")
        return 1
    if ecfg.spec_decode:
        s = engine.stats
        print(f"[serve] spec: steps={s.spec_steps} drafted={s.drafted_tokens} "
              f"accepted={s.accepted_tokens} rejected={s.rejected_drafts} "
              f"hist={dict(sorted(s.accept_len_hist.items()))}")
        if args.require_accepts and s.accepted_tokens == 0:
            print("[serve] FAIL: speculative decoding accepted 0 drafted "
                  "tokens under --require-accepts")
            return 1
    if args.host_serving:
        # epsilon: two pages of slack plus 10% of the host-served volume —
        # occasional BY-DESIGN promotions are tolerated (a host preference
        # bounced once by the step-5 balancer falls back to gpu placement
        # and legitimately promotes its prefix; COW pages may cross for a
        # gpu-pinned sibling), wholesale promotion of host-resident
        # prefixes is not
        page_bytes = page_tokens = 0
        if engine.prefix_cache is not None:
            page_bytes = engine.prefix_cache.page_nbytes()
            page_tokens = engine.prefix_cache.page
        served_pages = m.host_served_hit_tokens / max(page_tokens, 1)
        eps = int(page_bytes * (2 + 0.1 * served_pages))
        if m.inplace_host_hits <= 0:
            print("[serve] FAIL: no in-place host-served prefix hits "
                  "(inplace_host_hits == 0) under --host-serving")
            return 1
        if m.host_hit_pcie_bytes > eps:
            print(f"[serve] FAIL: host-resident prefix hits crossed PCIe "
                  f"({m.host_hit_pcie_bytes} B > eps {eps} B)")
            return 1
        print(f"[serve] host-serving OK: inplace_host_hits="
              f"{m.inplace_host_hits} host_served_hit_tokens="
              f"{m.host_served_hit_tokens} host_hit_pcie_bytes="
              f"{m.host_hit_pcie_bytes}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
