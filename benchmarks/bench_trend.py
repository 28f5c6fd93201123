"""Bench-trend gate: run the engine + prefix-cache smokes, write the
schema'd ``BENCH_engine.json`` summary at the REPO ROOT, and fail on a
perf-trajectory regression vs the checked-in baseline.

This is the CI ``bench-trend`` job's entry point (the summary file is
uploaded as a build artifact, so the trajectory is inspectable per commit).
Schema (``neo-bench-trend/v6``; documented in ``benchmarks/README.md``):

* ``engine.*_tok_s``      — smoke token throughputs (RECORDED, not gated:
  they are wall-times of whatever machine ran the job);
* ``engine.bubble_fraction`` — measured pipeline bubble of the
  micro-batched fastdecode smoke (GATED: must not regress past the
  checked-in baseline + tolerance — the structural-overlap headline);
* ``engine.microbatched_steps`` / ``engine.borrowed_lane_steps`` — unified
  lane-plan counters (GATED > 0: the splits must actually fire);
* ``prefix_cache.hit_rate`` / ``prefill_reduction`` — multiturn cache
  smoke (hit_rate GATED against baseline - tolerance);
* ``prefix_cache.host_served_hit_tokens`` / ``inplace_host_hits`` —
  zero-copy host-tier serving counters from the ``--host-serving`` section
  (GATED > 0: host-resident prefixes must be served in place, and the
  section itself fails on any host-hit PCIe bytes);
* ``serving.*`` — sustained-load A/B (closed-loop lockstep vs open-loop
  continuous batching with plan-ahead): goodput and p99 TTFT/TPOT for both
  loops (RECORDED — wall-clock latencies are machine-dependent), plus
  ``planahead_hits`` (GATED > 0: speculative plans must actually be
  adopted) and ``bitwise_identical`` (GATED: plan-ahead may never change
  greedy outputs);
* ``obs.tracing_overhead`` — fractional tok/s cost of structured tracing
  on the decode-heavy fastdecode smoke (GATED <= TRACING_OVERHEAD_TOL:
  the tracer must stay out of the engine's way);
* ``obs.reconcile_ok`` — the span timeline reproduces EngineStats' lane
  busy / overlap / bubble / swap-hidden / plan-ahead accounting (GATED
  true: the trace is a standing audit of every other gated number);
* ``sharded.*`` (v5) — the tensor-parallel A/B (``engine_sharded.py``,
  TP=1 vs TP=2 in this process, so on the CPU start it with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``): ``tp2_bitwise_ok`` (GATED:
  gather-TP may never change greedy outputs), ``swap_bytes_equal`` and
  ``stream_split_exact`` (GATED: per-shard copy streams must partition
  the TP=1 byte totals exactly), plus the recorded per-shard byte split;
* ``spec.*`` (v6) — the speculative-decoding A/B (``engine_real.py
  --spec-only``, low-concurrency fastdecode smoke): ``bitwise_ok`` (GATED:
  neither drafter may ever change greedy outputs), ``oracle_accepted``
  (GATED > 0: the verify pass must actually accept) and
  ``oracle_accept_len_hist`` (GATED: populated at length >= 1), plus
  ``oracle_speedup`` (GATED > 1: accepted tokens ride the verify pass
  instead of full engine iterations) and the recorded n-gram-drafter
  accept counters.

``--write-baseline`` refreshes ``benchmarks/BENCH_baseline.json`` (commit
the result deliberately — that is the trajectory being gated).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmarks.common import FIG_DIR, HERE
from repro.launch.compile_cache import setup_compile_cache

SCHEMA = "neo-bench-trend/v6"
REPO_ROOT = os.path.dirname(HERE)
BASELINE_PATH = os.path.join(HERE, "BENCH_baseline.json")
SUMMARY_PATH = os.path.join(REPO_ROOT, "BENCH_engine.json")

# Gate tolerances: bubble_fraction is a structural ratio (stable across
# machines), throughputs are not — only ratios/counters are gated.
BUBBLE_TOL = 0.05
HIT_RATE_TOL = 0.05
TRACING_OVERHEAD_TOL = 0.05


def _load(name: str) -> dict:
    with open(os.path.join(FIG_DIR, name)) as f:
        return json.load(f)


def collect(n: int) -> tuple[int, dict]:
    """Run the smokes (micro-batch, mixed-lane, prefix-cache) and collate
    their figure JSONs into the trend summary.  Returns (rc, summary)."""
    from benchmarks import engine_real, engine_sharded, prefix_cache
    from repro.launch.serve import run_sustained

    rc = 0
    rc |= engine_real.main(["--microbatch-only", "--n", str(n)])
    rc |= engine_real.main(["--mixed-lane-only"])
    rc |= engine_real.main(["--obs-only", "--n", str(n)])
    rc |= engine_real.main(["--spec-only", "--n", str(n)])
    rc |= prefix_cache.main(["--quick", "--host-serving"])
    rc |= engine_sharded.main([])
    sus = run_sustained(n=max(n, 12), rate=8.0, seed=0)

    er = _load("engine_real.json")
    pc = _load("prefix_cache.json")
    sh = _load("engine_sharded.json")
    mb_on = er["fastdecode_mb_on"]
    mb_off = er["fastdecode_mb_off"]
    mixed = er["mixed_pipelined"]
    summary = {
        "schema": SCHEMA,
        "arch": "qwen3-0.6b (smoke)",
        "engine": {
            "fastdecode_mb_on_tok_s": mb_on["token_throughput"],
            "fastdecode_mb_off_tok_s": mb_off["token_throughput"],
            "mixed_pipelined_tok_s": mixed["token_throughput"],
            "bubble_fraction": mb_on["bubble_fraction"],
            "bubble_fraction_serialized": mb_off["bubble_fraction"],
            "microbatched_steps": mb_on["microbatched_steps"],
            "borrowed_lane_steps": mixed["borrowed_lane_steps"],
            "lane_count_steps": mixed["lane_count_steps"],
        },
        "prefix_cache": {
            "hit_rate": pc["cache_on"]["hit_rate"],
            "prefill_reduction": pc["prefill_reduction"],
            "cache_on_tok_s": pc["cache_on"]["token_throughput"],
            # zero-copy host-tier serving (--host-serving section)
            "host_served_hit_tokens": pc["hs_cache_on"]["host_served_hit_tokens"],
            "inplace_host_hits": pc["hs_cache_on"]["inplace_host_hits"],
            "token_granular_extra_hit_tokens":
                pc["hs_token_granular_extra_hit_tokens"],
        },
        "serving": {
            "closed_goodput_rps": sus["closed"]["goodput_rps"],
            "open_goodput_rps": sus["open"]["goodput_rps"],
            "closed_ttft_p99_ms": sus["closed"]["ttft_p99_ms"],
            "open_ttft_p99_ms": sus["open"]["ttft_p99_ms"],
            "closed_tpot_p99_ms": sus["closed"]["tpot_p99_ms"],
            "open_tpot_p99_ms": sus["open"]["tpot_p99_ms"],
            "planahead_hits": sus["open"]["planahead_hits"],
            "planahead_replans": sus["open"]["planahead_replans"],
            "planahead_hidden_s": sus["open"]["planahead_hidden_s"],
            "bitwise_identical": sus["gates"]["bitwise_identical"],
        },
        "obs": {
            "tracing_off_tok_s": er["obs_tracing_off"]["token_throughput"],
            "tracing_on_tok_s": er["obs_tracing_on"]["token_throughput"],
            "tracing_overhead": er["obs_tracing_on"]["tracing_overhead"],
            "reconcile_ok": er["obs_tracing_on"]["reconcile_ok"],
            "trace_events": er["obs_tracing_on"]["trace_events"],
            "trace_dropped": er["obs_tracing_on"]["trace_dropped"],
        },
        "spec": {
            "bitwise_ok": er["spec_gates"]["bitwise_ok"],
            "oracle_speedup": er["spec_gates"]["oracle_speedup"],
            "oracle_accepted": er["spec_oracle"]["accepted_tokens"],
            "oracle_accept_len_hist": er["spec_oracle"]["accept_len_hist"],
            "ngram_drafted": er["spec_ngram"]["drafted_tokens"],
            "ngram_accepted": er["spec_ngram"]["accepted_tokens"],
            "spec_off_tok_s": er["spec_off"]["token_throughput"],
            "spec_ngram_tok_s": er["spec_ngram"]["token_throughput"],
            "spec_oracle_tok_s": er["spec_oracle"]["token_throughput"],
        },
        "sharded": {
            "tp2_bitwise_ok": sh["tp2_bitwise_ok"],
            "swap_bytes_equal": sh["swap_bytes_equal"],
            "stream_split_exact": sh["stream_split_exact"],
            "bytes_out": sh["bytes_out"],
            "bytes_in": sh["bytes_in"],
            "tp2_copy_streams": sh["tp2_copy_streams"],
            "tp1_tok_s": sh["tp1_tok_s"],
            "tp2_tok_s": sh["tp2_tok_s"],
        },
    }
    return rc, summary


def gate(summary: dict, baseline: dict) -> int:
    """Compare the fresh summary against the checked-in baseline; returns
    the number of regressions (0 == green)."""
    fails = 0
    b_eng, s_eng = baseline["engine"], summary["engine"]
    if s_eng["bubble_fraction"] > b_eng["bubble_fraction"] + BUBBLE_TOL:
        print(f"[bench_trend] FAIL: bubble_fraction regressed "
              f"{b_eng['bubble_fraction']} -> {s_eng['bubble_fraction']} "
              f"(tol {BUBBLE_TOL})")
        fails += 1
    if s_eng["microbatched_steps"] == 0:
        print("[bench_trend] FAIL: no micro-batched steps in the fastdecode "
              "smoke")
        fails += 1
    if s_eng["borrowed_lane_steps"] == 0:
        print("[bench_trend] FAIL: no borrowed-lane steps in the mixed-plan "
              "smoke")
        fails += 1
    b_pc, s_pc = baseline["prefix_cache"], summary["prefix_cache"]
    if s_pc["hit_rate"] < b_pc["hit_rate"] - HIT_RATE_TOL:
        print(f"[bench_trend] FAIL: prefix-cache hit_rate regressed "
              f"{b_pc['hit_rate']} -> {s_pc['hit_rate']} (tol {HIT_RATE_TOL})")
        fails += 1
    if s_pc.get("host_served_hit_tokens", 0) <= 0:
        print("[bench_trend] FAIL: no host-served hit tokens in the "
              "host-serving smoke")
        fails += 1
    if s_pc.get("inplace_host_hits", 0) <= 0:
        print("[bench_trend] FAIL: no in-place host hits in the "
              "host-serving smoke")
        fails += 1
    s_srv = summary.get("serving", {})
    if s_srv.get("planahead_hits", 0) <= 0:
        print("[bench_trend] FAIL: plan-ahead never adopted a speculative "
              "plan in the sustained-load smoke")
        fails += 1
    if not s_srv.get("bitwise_identical", False):
        print("[bench_trend] FAIL: plan-ahead changed greedy outputs in the "
              "sustained-load smoke")
        fails += 1
    s_sh = summary.get("sharded", {})
    if not s_sh.get("tp2_bitwise_ok", False):
        print("[bench_trend] FAIL: TP=2 greedy outputs diverge from TP=1 "
              "in the sharded smoke")
        fails += 1
    if not s_sh.get("swap_bytes_equal", False):
        print("[bench_trend] FAIL: TP=2 swap byte totals differ from TP=1 "
              "in the sharded smoke")
        fails += 1
    if not s_sh.get("stream_split_exact", False):
        print("[bench_trend] FAIL: per-shard copy-stream bytes do not "
              "partition the totals in the sharded smoke")
        fails += 1
    s_sp = summary.get("spec", {})
    if not s_sp.get("bitwise_ok", False):
        print("[bench_trend] FAIL: speculative decoding changed greedy "
              "outputs in the spec smoke")
        fails += 1
    if s_sp.get("oracle_accepted", 0) <= 0:
        print("[bench_trend] FAIL: the verify pass accepted 0 drafted "
              "tokens in the spec smoke")
        fails += 1
    hist = s_sp.get("oracle_accept_len_hist", {})
    if not any(int(k) >= 1 and v > 0 for k, v in hist.items()):
        print("[bench_trend] FAIL: accepted-length histogram empty at >= 1 "
              "in the spec smoke")
        fails += 1
    if s_sp.get("oracle_speedup", 0.0) <= 1.0:
        print(f"[bench_trend] FAIL: no speculative throughput win "
              f"(oracle_speedup={s_sp.get('oracle_speedup')})")
        fails += 1
    s_obs = summary.get("obs", {})
    if s_obs.get("tracing_overhead", 0.0) > TRACING_OVERHEAD_TOL:
        print(f"[bench_trend] FAIL: tracing overhead "
              f"{s_obs['tracing_overhead']:.2%} exceeds "
              f"{TRACING_OVERHEAD_TOL:.0%} of untraced tok/s")
        fails += 1
    if not s_obs.get("reconcile_ok", False):
        print("[bench_trend] FAIL: span timeline disagrees with EngineStats "
              "(reconcile) in the tracing smoke")
        fails += 1
    if not fails:
        print(f"[bench_trend] OK: bubble {s_eng['bubble_fraction']} "
              f"(baseline {b_eng['bubble_fraction']}), hit_rate "
              f"{s_pc['hit_rate']} (baseline {b_pc['hit_rate']})")
    return fails


def main(argv=None) -> int:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=12,
                    help="requests per engine smoke run")
    ap.add_argument("--write-baseline", action="store_true",
                    help="refresh benchmarks/BENCH_baseline.json instead of "
                         "gating against it")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    args = ap.parse_args(argv)

    rc, summary = collect(args.n)
    with open(SUMMARY_PATH, "w") as f:
        json.dump(summary, f, indent=1)
        f.write("\n")
    print(f"[bench_trend] wrote {SUMMARY_PATH}")
    if rc:
        print("[bench_trend] FAIL: a smoke gate failed (see above)")
        return rc
    if args.write_baseline:
        with open(args.baseline, "w") as f:
            json.dump(summary, f, indent=1)
            f.write("\n")
        print(f"[bench_trend] baseline refreshed: {args.baseline}")
        return 0
    if not os.path.exists(args.baseline):
        print(f"[bench_trend] FAIL: no baseline at {args.baseline} "
              f"(run with --write-baseline and commit it)")
        return 1
    with open(args.baseline) as f:
        baseline = json.load(f)
    return 1 if gate(summary, baseline) else 0


if __name__ == "__main__":
    sys.exit(main())
