#!/usr/bin/env python3
"""Bring-up smoke of NeoEngine's served path on one TPU chip.

A smoke run, not a benchmark: it shows that the served path runs and gives
the right answers on the chip; the times it prints are for orientation only.

It drives ``NeoEngine`` through ``repro.launch.serve.run_online`` (the loop
``python -m repro.launch.serve --arrivals poisson`` runs) on qwen3-0.6b at
its published widths: 28 layers, d_model 1024, GQA 16/8, head_dim 128,
vocab 151,936, bf16, weights made at random from ``--seed``.  Eight
requests (prompts of 128-512 tokens, 32 output tokens each) overflow a
64-page device pool, so the ``neo`` policy places rows on the host: the
per-layer ``io_callback`` into ``HostAttention`` fires inside the TPU
program and swaps move pages.

Phases, each checking what it exercised (any failed check exits non-zero):

1. ``kernel`` — the decode-attention implementation the platform picks is
   the compiled Pallas kernel (a ``tpu_custom_call``), and one call of it
   agrees with the float32 ``paged_decode_ref`` within a bf16 tolerance.
2. ``serve`` — every request finishes with its requested token count, every
   sampled logits row is finite, host attention ran (``host_busy_time > 0``
   and a ``cpu0``/``cpu1`` row in the plans) and swap bytes are > 0.
3. ``serial`` — the same prompts with ``pipeline=False``: greedy outputs
   equal those of phase 2 (the repo's pipelined-vs-serial contract).

``--tp 4`` runs only the gather-TP phase on a four-chip host: the same
requests at ``tp=4`` and ``tp=1`` in this one process, whose greedy outputs
must be equal.  ``--rehearse`` swaps in the smoke config and allows the CPU
(rehearsal only; Pallas then runs in interpret mode).

    python chip_smoke.py                 # one chip
    python chip_smoke.py --tp 4          # four chips
    JAX_PLATFORMS=cpu PYTHONPATH=src python chip_smoke.py --rehearse

The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import EngineConfig  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core.engine import NeoEngine  # noqa: E402
from repro.kernels.paged_decode import ops as paged_ops  # noqa: E402
from repro.kernels.paged_decode.ref import paged_decode_ref  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.serve import run_online  # noqa: E402
from repro.models.api import get_model  # noqa: E402
from repro.serving.traces import TraceRequest  # noqa: E402

ARCH = "qwen3-0.6b"
N_REQUESTS = 8
PROMPT_RANGE = (128, 512)
OUTPUT_LEN = 32
DEVICE_PAGES = 64  # 1,024 tokens at 112 KiB each: the requests overflow it
HOST_PAGES = 256
MAX_BATCH_TOKENS = 2048
KERNEL_ATOL = 2e-2  # bf16 inputs and output, float32 reference


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"[chip_smoke]   ok: {what}")


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.lookups = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.lookups += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def line(self) -> str:
        return (f"compile_s={self.seconds:.3f} compiles={self.compiles} "
                f"cache_hits={self.hits}/{self.lookups}")


def make_trace(seed: int) -> list:
    """Open-loop requests that all arrive at t=0, so every run of the same
    seed offers the same prompts in the same order."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1, size=N_REQUESTS)
    return [TraceRequest(0.0, int(n), OUTPUT_LEN) for n in lens]


def serve(cfg, params, seed: int, **engine_kw) -> dict:
    """One ``run_online`` pass; returns outputs and the engine's counters.

    Every emitted token's logits row is checked for finiteness and kept as
    a digest, its top logit and its top-2 margin, so two runs that disagree
    can say where their logits first differed, by how much, and how close
    the flipped choice was."""
    ecfg = EngineConfig(device_pool_pages=DEVICE_PAGES,
                        host_pool_pages=HOST_PAGES,
                        max_batch_tokens=MAX_BATCH_TOKENS, policy="neo",
                        seed=seed, **engine_kw)
    engine = NeoEngine(cfg, ecfg, params=params)
    finite = {"rows": 0, "bad": 0}
    logit_log = {}
    emit = engine._emit

    def checked_emit(req, logits, *rest):
        row = np.asarray(logits, np.float32)
        finite["rows"] += 1
        finite["bad"] += int(not np.all(np.isfinite(row)))
        top2 = np.partition(row, -2)[-2:]
        logit_log[(req.rid, len(req.out_tokens))] = (
            hashlib.blake2b(row.tobytes(), digest_size=8).digest(),
            float(top2[1] - top2[0]), float(top2[1]))
        return emit(req, logits, *rest)

    engine._emit = checked_emit
    try:
        t0 = time.perf_counter()
        metrics = run_online(engine, make_trace(seed), vocab=cfg.vocab_size,
                             seed=seed)
        wall = time.perf_counter() - t0
    finally:
        engine.close()
    st = engine.stats
    outputs = {rid: list(r.out_tokens) for rid, r in engine.requests.items()}
    host_plans = sum(
        1 for p in st.plans
        if re.search(r"dec_cpu0=[1-9]|dec_cpu1=[1-9]", p))
    return {
        "outputs": outputs,
        "logits": logit_log,
        "records": metrics.records,
        "finite": finite,
        "wall_s": wall,
        "steps": st.iterations,
        "tokens": st.tokens_out,
        "mode_counts": dict(st.mode_counts),
        "host_busy_s": st.host_busy_time,
        "host_plans": host_plans,
        "offloaded_decodes": st.offloaded_decodes,
        "swap_bytes": int(engine.pool.swap_bytes),
        "decode_impl": (engine.executor.impl, engine.executor.interpret),
    }


def report(tag: str, r: dict) -> None:
    print(f"[chip_smoke] {tag}: steps={r['steps']} tokens={r['tokens']} "
          f"wall_s={r['wall_s']:.3f} host_busy_s={r['host_busy_s']:.3f} "
          f"offloaded_decodes={r['offloaded_decodes']} "
          f"swap_bytes={r['swap_bytes']}")
    print(f"[chip_smoke] {tag}: modes={r['mode_counts']} "
          f"plans_with_host_rows={r['host_plans']} "
          f"decode_impl={r['decode_impl'][0]}"
          f"{' (interpret)' if r['decode_impl'][1] else ''}")


def first_divergence(a: dict, b: dict) -> str:
    """Where two runs' greedy outputs first differ, where their logits first
    differed (rows compared bitwise; margin = top-1 minus top-2), and the
    largest top-1 logit difference over rows fed the same tokens."""
    x_out, y_out = a["outputs"], b["outputs"]
    for rid in sorted(x_out):
        x, y = x_out[rid], y_out.get(rid, [])
        if x != y:
            i = next((i for i, (p, q) in enumerate(zip(x, y)) if p != q),
                     min(len(x), len(y)))
            break
    else:
        return "none"
    la, lb = a["logits"], b["logits"]
    differ = [k for k in sorted(la) if k in lb and la[k][0] != lb[k][0]]
    first = (f"request {differ[0][0]} token {differ[0][1]}" if differ
             else "nowhere")
    margins = (f"{la[(rid, i)][1]:.4g} vs {lb[(rid, i)][1]:.4g}"
               if (rid, i) in la and (rid, i) in lb else "n/a")
    same_input = [k for k in la if k in lb
                  and x_out[k[0]][:k[1]] == y_out.get(k[0], [])[:k[1]]]
    top1 = max((abs(la[k][2] - lb[k][2]) for k in same_input), default=0.0)
    return (f"request {rid} at token {i} ({len(x)} vs {len(y)} tokens); "
            f"logits rows first differ at {first}, {len(differ)}/{len(la)} "
            f"rows differ; top-2 margin at the flip {margins}; max "
            f"|top-1 logit diff| over {len(same_input)} same-input rows "
            f"{top1:.4g}")


def phase_kernel(cfg, interpret: bool, on_tpu: bool) -> None:
    """One call of the paged-decode kernel at the config's widths vs the
    float32 reference."""
    print("[chip_smoke] phase kernel")
    if on_tpu:
        check(paged_ops.platform_impl() == "pallas",
              "the platform picks the Pallas decode kernel")
    B, MP, page, P = 8, 16, cfg.kv_block_size, DEVICE_PAGES
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.activation_dtype)
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (B, H, hd), jnp.float32).astype(dt)
    kp = jax.random.normal(keys[1], (P, page, KV, hd), jnp.float32).astype(dt)
    vp = jax.random.normal(keys[2], (P, page, KV, hd), jnp.float32).astype(dt)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(rng.integers(1, P, size=(B, MP)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, MP * page + 1, size=B), jnp.int32)
    if on_tpu:
        hlo = jax.jit(lambda *a: paged_ops.paged_decode_attention(*a)).lower(
            q, kp, vp, tables, lens).as_text()
        check("tpu_custom_call" in hlo,
              "the default decode attention lowers to a tpu_custom_call")
    out = paged_ops.paged_decode_attention(
        q, kp, vp, tables, lens, impl="pallas", interpret=interpret)
    ref = paged_decode_ref(q.astype(jnp.float32), kp.astype(jnp.float32),
                           vp.astype(jnp.float32), tables, lens)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    print(f"[chip_smoke]   kernel B={B} H={H} KV={KV} hd={hd} page={page} "
          f"pages/row={MP} dtype={dt.name}: max|pallas - ref|={err:.3e}")
    check(bool(np.isfinite(err)) and err <= KERNEL_ATOL,
          f"Pallas paged decode within {KERNEL_ATOL} of the float32 reference")


def phase_serve(cfg, params, seed: int, on_tpu: bool) -> None:
    print("[chip_smoke] phase serve (pipelined)")
    piped = serve(cfg, params, seed)
    report("serve", piped)
    if on_tpu:
        check(piped["decode_impl"] == ("pallas", False),
              "the engine's device decode runs the compiled Pallas kernel")
    recs = piped["records"]
    check(len(recs) == N_REQUESTS and all(
        rec.status == "finished"
        and len(piped["outputs"][rec.rid]) == OUTPUT_LEN for rec in recs),
        f"all {N_REQUESTS} requests finished with {OUTPUT_LEN} tokens")
    check(piped["finite"]["rows"] > 0 and piped["finite"]["bad"] == 0,
          f"all {piped['finite']['rows']} sampled logits rows are finite")
    check(piped["host_busy_s"] > 0 and piped["host_plans"] > 0,
          "host attention ran (host_busy_time > 0, cpu0/cpu1 rows planned)")
    check(piped["swap_bytes"] > 0, "swaps moved bytes")

    print("[chip_smoke] phase serial (pipeline=False)")
    serial = serve(cfg, params, seed, pipeline=False)
    report("serial", serial)
    check(serial["outputs"] == piped["outputs"],
          "pipelined greedy outputs equal the serial path's "
          f"(first divergence: {first_divergence(piped, serial)})")


def phase_tp(cfg, params, seed: int, tp: int) -> None:
    print(f"[chip_smoke] phase tp (tp={tp} vs tp=1)")
    n_dev = len(jax.devices())
    if n_dev < tp:
        raise SmokeFailure(f"--tp {tp} needs {tp} devices, found {n_dev}")
    base = serve(cfg, params, seed)
    report("tp1", base)
    sharded = serve(cfg, params, seed, tp=tp)
    report(f"tp{tp}", sharded)
    check(all(len(o) == OUTPUT_LEN for o in sharded["outputs"].values()),
          f"all tp={tp} requests finished with {OUTPUT_LEN} tokens")
    check(sharded["finite"]["bad"] == 0, f"tp={tp} logits are finite")
    check(sharded["outputs"] == base["outputs"],
          f"tp={tp} greedy outputs equal tp=1's (first divergence: "
          f"{first_divergence(base, sharded)})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the gather-TP phase at this degree vs tp=1")
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke config, CPU allowed (rehearsal only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    on_tpu = platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r} "
              f"({kind})", file=sys.stderr)
        return 2
    cache_dir = setup_compile_cache()
    compiles = CompileLog()
    cfg = get_smoke_config(ARCH) if args.rehearse else get_config(ARCH)
    print(f"[chip_smoke] SMOKE RUN, not a benchmark: {cfg.name} "
          f"({cfg.num_layers}L d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} head_dim={cfg.head_dim} vocab={cfg.vocab_size} "
          f"{cfg.activation_dtype}), seed={args.seed}")
    print(f"[chip_smoke] device: platform={platform} kind={kind} "
          f"count={len(devices)} compile_cache={cache_dir}")

    t0 = time.perf_counter()
    try:
        params = jax.jit(get_model(cfg).init)(jax.random.key(args.seed))
        jax.block_until_ready(params)
        print(f"[chip_smoke] params: "
              f"{sum(x.size for x in jax.tree.leaves(params))} "
              f"in {time.perf_counter() - t0:.3f}s")
        if args.tp > 1:
            phase_tp(cfg, params, args.seed, args.tp)
        else:
            phase_kernel(cfg, interpret=not on_tpu, on_tpu=on_tpu)
            phase_serve(cfg, params, args.seed, on_tpu)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        print(f"[chip_smoke] {compiles.line()} total_wall_s="
              f"{time.perf_counter() - t0:.3f}")
        for d in devices:
            stats = d.memory_stats() or {}
            print(f"[chip_smoke] device {d.id} ({d.device_kind}): "
                  f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
