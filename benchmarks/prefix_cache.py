"""Prefix-cache benchmark: multi-turn conversations, cache on vs off.

Drives the real NeoEngine CLOSED-LOOP over the shared-system-prompt
``multiturn`` trace — a conversation's next turn is submitted only after the
previous turn finishes (the user "reads the answer"), which is where prefix
caching pays: every turn re-submits the whole history and the cache serves
the already-decoded prefix from tree pages instead of re-prefilling it.

Reported per config:

* ``prefill_tok``  — prefill tokens actually computed (suffix only on hits);
  the cache must cut this >= 2x on the multiturn trace.
* ``tok/s``        — end-to-end token throughput of the timed section.
* hit/promotion/demotion/eviction counters from :class:`PrefixCacheStats`.

Cache-off results are the compat baseline: greedy outputs are checked
identical between the two runs (the cache must change WHAT is computed, not
what is produced).
"""

from __future__ import annotations

import argparse
import time
from collections import defaultdict

import jax

from benchmarks.common import print_table, save_json
from repro.launch.compile_cache import setup_compile_cache
from repro.config import EngineConfig
from repro.configs import get_smoke_config
from repro.core.engine import NeoEngine
from repro.serving.traces import multiturn_trace


def build_conversations(n: int, turns: int, seed: int, vocab: int):
    """[(turn_order_key, prompt, output_len)] grouped by conversation."""
    trace = multiturn_trace(
        n, rate=1e9, seed=seed, turns=turns, vocab=min(vocab, 500),
        # prefill-heavy shape: long shared histories, short answers (the
        # agent/chat regime the prefix cache targets)
        system_len=384, context_len=96, user_len_median=64,
        output_median=10, max_output=16,
    )
    convs = defaultdict(list)
    for t in trace:
        convs[t.conv].append(t)
    for c in convs.values():
        # turn order within a conversation = prompt-length order (each turn
        # strictly extends the previous one)
        c.sort(key=lambda t: t.prompt_len)
    return list(convs.values())


def drive(eng: NeoEngine, conversations):
    """Closed-loop driver: round-robin over conversations; a conversation's
    next turn goes in only after its previous turn finished (different
    conversations still batch together).  Returns (outputs, total_tokens)."""
    outputs = {}
    total_tokens = 0
    cursors = [0] * len(conversations)
    pending = {}  # rid -> conv index
    while True:
        busy = set(pending.values())
        for ci, conv in enumerate(conversations):
            if cursors[ci] < len(conv) and ci not in busy:
                t = conv[cursors[ci]]
                rid = eng.submit(t.prompt, t.output_len)
                pending[rid] = ci
                busy.add(ci)
                cursors[ci] += 1
                total_tokens += t.prompt_len + t.output_len
        if not pending:
            break
        eng.step(now=eng.clock + 1e-3)
        for rid in list(pending):
            req = eng.requests[rid]
            if req.state.name in ("FINISHED", "ABORTED"):
                outputs[(pending.pop(rid), len(req.prompt))] = list(req.out_tokens)
    return outputs, total_tokens


def run(prefix_cache: bool, conversations, warmup, *, params, cfg,
        device_pages: int, host_pages: int, seed: int = 0,
        token_granular: bool = True):
    from repro.core.engine import EngineStats
    from repro.core.prefix_cache import PrefixCacheStats

    ecfg = EngineConfig(
        device_pool_pages=device_pages, host_pool_pages=host_pages,
        max_batch_tokens=2048, policy="neo", prefix_cache=prefix_cache,
        prefix_token_granular=token_granular,
        seed=seed,
    )
    eng = NeoEngine(cfg, ecfg, params=params)
    # warmup: same-shaped disjoint conversations compile every graph bucket
    # (incl. the suffix-prefill buckets) and settle the tree into steady
    # state, so the timed section measures sustained serving throughput
    drive(eng, warmup)
    eng.stats = EngineStats()
    if eng.prefix_cache is not None:
        eng.prefix_cache.stats = PrefixCacheStats()

    t0 = time.perf_counter()
    outputs, total_tokens = drive(eng, conversations)
    wall = time.perf_counter() - t0
    stats = eng.prefix_cache.stats if eng.prefix_cache else None
    res = {
        "prefix_cache": prefix_cache,
        "prefill_tok": eng.stats.prefill_tokens,
        "token_throughput": round(total_tokens / wall, 1),
        "wall_s": round(wall, 2),
        "iterations": eng.stats.iterations,
        "hit_rate": round(stats.hit_rate, 3) if stats else 0.0,
        "hit_tokens": stats.hit_tokens if stats else 0,
        "promoted": stats.promoted_pages if stats else 0,
        "demoted": stats.demoted_pages if stats else 0,
        "evicted": stats.evicted_pages if stats else 0,
        "cow": stats.cow_copies if stats else 0,
        # zero-copy host-tier serving
        "inplace_host_hits": stats.inplace_host_hits if stats else 0,
        "host_served_hit_tokens": stats.host_served_hit_tokens if stats else 0,
        "host_hit_pcie_bytes": stats.host_hit_pcie_bytes if stats else 0,
    }
    eng.close()
    return res, outputs


def run_host_serving(conversations, warmup, *, params, cfg,
                     host_pages: int) -> tuple:
    """``--host-serving`` section, two gates:

    1. **Zero-PCIe host serving** — a multiturn closed loop whose device
       pool is far too small for the conversation histories, so prefills
       land on the CPU queue and their host-resident prefixes must be
       served IN PLACE: ``inplace_host_hits > 0``, host-hit PCIe bytes and
       ``promoted_pages`` stay 0, greedy outputs bitwise identical to
       cache-off.
    2. **Token-granular vs page-aligned** — same trace (histories extend at
       arbitrary, non-page-aligned lengths), cache on in both modes: the
       token-granular radix must serve STRICTLY more hit tokens.

    Returns (rc, results-dict).
    """
    # a device pool smaller than one history forces the host tier to SERVE
    small_dev = 16
    common = dict(params=params, cfg=cfg, device_pages=small_dev,
                  host_pages=host_pages)
    rows, results = [], {}
    off, off_out = run(False, conversations, warmup, **common)
    on, on_out = run(True, conversations, warmup, **common)
    aligned, _ = run(True, conversations, warmup, token_granular=False,
                     **common)
    for key, r in (("hs_cache_off", off), ("hs_cache_on", on),
                   ("hs_page_aligned", aligned)):
        results[key] = r
        rows.append([key, r["prefill_tok"], r["hit_rate"], r["hit_tokens"],
                     r["inplace_host_hits"], r["host_served_hit_tokens"],
                     r["host_hit_pcie_bytes"], r["promoted"]])
    print("=== Host-tier serving (multiturn closed-loop, device pool "
          f"{small_dev} pages) ===")
    print_table(["config", "prefill tok", "hit rate", "hit tok", "inplace",
                 "host served", "hit PCIe B", "promo"], rows)

    rc = 0
    same = off_out == on_out
    results["hs_outputs_identical"] = same
    if not same:
        print("FAIL: host-served outputs differ from cache-off outputs")
        rc = 1
    if on["inplace_host_hits"] <= 0:
        print("FAIL: no in-place host-served prefix hits "
              "(inplace_host_hits == 0)")
        rc = 1
    if on["host_hit_pcie_bytes"] > 0 or on["promoted"] > 0:
        print(f"FAIL: host-resident prefix hits crossed PCIe "
              f"({on['host_hit_pcie_bytes']} B, promoted "
              f"{on['promoted']} pages)")
        rc = 1
    gain = on["hit_tokens"] - aligned["hit_tokens"]
    results["hs_token_granular_extra_hit_tokens"] = gain
    print(f"token-granular extra hit tokens vs page-aligned: {gain}")
    if gain <= 0:
        print("FAIL: token-granular matching did not increase hit tokens "
              "over page-aligned matching")
        rc = 1
    return rc, results


def main(argv=None) -> int:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=12, help="total turns")
    ap.add_argument("--turns", type=int, default=4, help="turns/conversation")
    ap.add_argument("--device-pages", type=int, default=96)
    ap.add_argument("--host-pages", type=int, default=256)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--host-serving", action="store_true",
                    help="also run the zero-copy host-serving section: "
                         "in-place host hits with 0 promotion PCIe bytes + "
                         "token-granular vs page-aligned hit-token gate")
    args = ap.parse_args(argv)
    n = 8 if args.quick else args.n

    cfg = get_smoke_config("qwen3-0.6b")
    from repro.models.api import get_model

    params = get_model(cfg).init(jax.random.key(0))
    conversations = build_conversations(n, args.turns, seed=0, vocab=cfg.vocab_size)
    warmup = build_conversations(max(4, n // 2), args.turns, seed=7,
                                 vocab=cfg.vocab_size)

    rows, results = [], {}
    outs = {}
    for cache in (False, True):
        key = "cache_on" if cache else "cache_off"
        r, outs[cache] = run(cache, conversations, warmup, params=params,
                             cfg=cfg, device_pages=args.device_pages,
                             host_pages=args.host_pages)
        results[key] = r
        rows.append([key, r["prefill_tok"], r["token_throughput"], r["wall_s"],
                     r["hit_rate"], r["hit_tokens"], r["promoted"],
                     r["demoted"], r["evicted"], r["cow"]])
    print("=== Prefix cache (multiturn closed-loop, smoke qwen3-0.6b) ===")
    print_table(["config", "prefill tok", "tok/s", "wall s", "hit rate",
                 "hit tok", "promo", "demo", "evict", "cow"], rows)

    same = outs[False] == outs[True]
    reduction = results["cache_off"]["prefill_tok"] / max(
        1, results["cache_on"]["prefill_tok"])
    print(f"prefill-token reduction: {reduction:.2f}x; "
          f"outputs identical: {same}")
    results["prefill_reduction"] = round(reduction, 2)
    results["outputs_identical"] = same

    rc = 0
    if args.host_serving:
        rc, hs_results = run_host_serving(
            conversations, warmup, params=params, cfg=cfg,
            host_pages=args.host_pages)
        results.update(hs_results)

    save_json("prefix_cache.json", results)
    if not same:
        print("FAIL: cached outputs differ from cold outputs")
        return 1
    if reduction < 2.0:
        print("FAIL: prefill-token reduction < 2x on the multiturn trace")
        return 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
