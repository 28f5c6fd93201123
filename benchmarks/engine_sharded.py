"""Tensor-parallel serving A/B: TP=2 vs TP=1 over this process's devices.

Gather-TP column-shards the QKV/gate/up projections and all-gathers (a pure
concat) before the replicated O/down projections, so every cross-shard
combine is reduction-free — greedy decode at TP=2 must be BITWISE identical
to TP=1, and the per-shard copy streams must partition the swap bytes
exactly.  This smoke runs both engines on the same swap-heavy trace, in this
process and over the devices it already holds (a child process could not
reach a chip this process holds), and gates:

* ``tp2_bitwise_ok`` — greedy outputs identical across TP=1/TP=2;
* ``swap_bytes_equal`` — PCIe byte totals (out + in) identical;
* ``stream_split`` — TP=2 records per-shard copy-stream bytes
  (``out0``/``out1``/...) that sum exactly to the direction totals.

On the CPU, start it with two or more host devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.engine_sharded

Results land in ``experiments/figures/engine_sharded.json`` and feed the
``sharded`` section of ``bench_trend``'s summary.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from benchmarks.common import print_table, save_json
from repro.config import EngineConfig
from repro.configs import get_smoke_config
from repro.core.engine import NeoEngine
from repro.core.request import RequestState
from repro.launch.compile_cache import setup_compile_cache


def _run_engine(tp: int, n: int) -> dict:
    cfg = get_smoke_config("qwen3-0.6b")
    ecfg = EngineConfig(device_pool_pages=10, host_pool_pages=128,
                        max_batch_tokens=1024, policy="neo", tp=tp)
    eng = NeoEngine(cfg, ecfg)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, size=24 + 3 * i).tolist(), 12)
            for i in range(n)]
    t0 = time.perf_counter()
    for _ in range(600):
        eng.step()
        if all(eng.requests[r].state == RequestState.FINISHED for r in rids):
            break
    wall = time.perf_counter() - t0
    toks = sum(len(eng.requests[r].out_tokens) for r in rids)
    ts = eng.transfer.stats
    res = {
        "outputs": {str(r): list(map(int, eng.requests[r].out_tokens)) for r in rids},
        "swap_bytes": int(eng.pool.swap_bytes),
        "bytes_out": int(ts.bytes_out),
        "bytes_in": int(ts.bytes_in),
        "bytes_by_stream": {k: int(v) for k, v in ts.bytes_by_stream.items()},
        "tok_s": toks / max(wall, 1e-9),
    }
    eng.close()
    return res


def run(n: int = 6) -> dict:
    if len(jax.devices()) < 2:
        raise RuntimeError(
            "the TP=2 A/B needs >= 2 devices in this process; on the CPU "
            "start with XLA_FLAGS=--xla_force_host_platform_device_count=8")
    tp1, tp2 = _run_engine(1, n), _run_engine(2, n)
    streams = tp2["bytes_by_stream"]
    out_split = {k: v for k, v in streams.items() if k.startswith("out")}
    in_split = {k: v for k, v in streams.items() if k.startswith("in")}
    res = {
        "tp2_bitwise_ok": tp1["outputs"] == tp2["outputs"],
        "swap_bytes_equal": (
            tp1["swap_bytes"] == tp2["swap_bytes"]
            and tp1["bytes_out"] == tp2["bytes_out"]
            and tp1["bytes_in"] == tp2["bytes_in"]),
        "swap_bytes": tp1["swap_bytes"],
        "bytes_out": tp1["bytes_out"],
        "bytes_in": tp1["bytes_in"],
        "tp2_copy_streams": streams,
        "stream_split_exact": (
            sum(out_split.values()) == tp2["bytes_out"]
            and sum(in_split.values()) == tp2["bytes_in"]
            and len(out_split) == 2),
        "tp1_tok_s": round(tp1["tok_s"], 1),
        "tp2_tok_s": round(tp2["tok_s"], 1),
    }
    return res


def main(argv=None) -> int:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=6, help="requests per A/B run")
    args = ap.parse_args(argv)

    res = run(args.n)
    print_table(
        ["gate", "value"],
        [["tp2_bitwise_ok", res["tp2_bitwise_ok"]],
         ["swap_bytes_equal", res["swap_bytes_equal"]],
         ["stream_split_exact", res["stream_split_exact"]],
         ["bytes_out (both)", res["bytes_out"]],
         ["tp2_copy_streams", res["tp2_copy_streams"]],
         ["tp1 tok/s", res["tp1_tok_s"]],
         ["tp2 tok/s", res["tp2_tok_s"]]])
    path = save_json("engine_sharded.json", res)
    print(f"[engine_sharded] wrote {path}")
    rc = 0
    if not res["tp2_bitwise_ok"]:
        print("[engine_sharded] FAIL: TP=2 greedy outputs diverge from TP=1")
        rc = 1
    if not res["swap_bytes_equal"]:
        print("[engine_sharded] FAIL: TP=2 swap byte totals differ from TP=1")
        rc = 1
    if not res["stream_split_exact"]:
        print("[engine_sharded] FAIL: per-shard copy-stream bytes do not "
              "partition the direction totals")
        rc = 1
    if res["bytes_out"] <= 0:
        print("[engine_sharded] FAIL: the A/B trace never swapped; gates "
              "are vacuous")
        rc = 1
    if rc == 0:
        print("[engine_sharded] OK: TP=2 bitwise-identical with exact "
              "per-shard byte split")
    return rc


if __name__ == "__main__":
    sys.exit(main())
