"""Kernel micro-benchmarks (§4): wall-time of the jnp reference paths on this
host plus interpret-mode Pallas validation, and the structural VMEM/MXU
accounting of each Pallas kernel's BlockSpec tiling.

On-TPU wall times cannot be measured here; the structural table shows each
kernel's working set fits VMEM (16 MB/core) and its tiles are MXU-aligned.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import print_table, save_json
from repro.launch.compile_cache import setup_compile_cache


def timeit(fn, *args, iters=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters


def bench_paged_decode():
    from repro.kernels.paged_decode.ops import paged_decode_attention

    rng = np.random.default_rng(0)
    B, H, KV, hd, page = 4, 8, 4, 64, 16
    P = 256
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KV, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KV, hd)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, P, size=(B, 16)), jnp.int32)
    lens = jnp.asarray(rng.integers(32, 256, size=(B,)), jnp.int32)
    t_ref = timeit(lambda *a: paged_decode_attention(*a, impl="ref"), q, kp, vp, bt, lens)
    # interpret-mode correctness delta
    o_ref = paged_decode_attention(q, kp, vp, bt, lens, impl="ref")
    o_pal = paged_decode_attention(q, kp, vp, bt, lens, impl="pallas", interpret=True)
    err = float(jnp.max(jnp.abs(o_ref - o_pal)))
    # structural: per-block VMEM = q tile + one kv page group
    vmem = (H * hd * 4) + 2 * (page * KV * hd * 4)
    return ["paged_decode", f"{t_ref * 1e3:.2f}ms", f"{err:.1e}", f"{vmem / 1e3:.0f}KB", "128-lane"]


def bench_flash_prefill():
    from repro.kernels.flash_prefill.ops import flash_prefill

    rng = np.random.default_rng(0)
    B, S, H, KV, hd = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    t_ref = timeit(lambda *a: flash_prefill(*a, impl="ref"), q, k, v)
    o_ref = flash_prefill(q, k, v, impl="ref")
    o_pal = flash_prefill(q, k, v, impl="pallas", interpret=True, blk_q=128, blk_k=128)
    err = float(jnp.max(jnp.abs(o_ref - o_pal)))
    vmem = (128 * hd * 4) * 2 + 2 * (128 * hd * 4)
    return ["flash_prefill", f"{t_ref * 1e3:.2f}ms", f"{err:.1e}", f"{vmem / 1e3:.0f}KB", "128x128 MXU"]


def bench_rwkv6():
    from repro.kernels.rwkv6_scan.ops import rwkv6_scan

    rng = np.random.default_rng(0)
    B, T, H, N = 1, 64, 4, 32
    r, k, v, w = (jnp.asarray(rng.normal(size=(B, T, H, N)), jnp.float32) for _ in range(4))
    w = jnp.exp(-jnp.exp(w))  # decay in (0,1)
    u = jnp.asarray(rng.normal(size=(H, N)), jnp.float32)
    s0 = jnp.zeros((B, H, N, N), jnp.float32)
    t_ref = timeit(lambda *a: rwkv6_scan(*a, impl="scan")[0], r, k, v, w, u, s0)
    y0, _ = rwkv6_scan(r, k, v, w, u, s0, impl="scan")
    y1, _ = rwkv6_scan(r, k, v, w, u, s0, impl="pallas", interpret=True)
    err = float(jnp.max(jnp.abs(y0 - y1)))
    vmem = 4 * (64 * N * 4) + N * N * 4
    return ["rwkv6_scan", f"{t_ref * 1e3:.2f}ms", f"{err:.1e}", f"{vmem / 1e3:.0f}KB", "chunked scan"]


def bench_mamba2():
    from repro.kernels.mamba2_ssd.ops import mamba2_ssd

    rng = np.random.default_rng(0)
    B, T, H, P_, N = 1, 64, 2, 32, 32
    x = jnp.asarray(rng.normal(size=(B, T, H, P_)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, T, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    s0 = jnp.zeros((B, H, P_, N), jnp.float32)
    t_ref = timeit(lambda *a: mamba2_ssd(*a, impl="scan")[0], x, dt, A, Bm, C, D, s0)
    y0, _ = mamba2_ssd(x, dt, A, Bm, C, D, s0, impl="scan")
    y1, _ = mamba2_ssd(x, dt, A, Bm, C, D, s0, impl="pallas", interpret=True)
    err = float(jnp.max(jnp.abs(y0 - y1)))
    vmem = (64 * P_ * 4) * 2 + P_ * N * 4
    return ["mamba2_ssd", f"{t_ref * 1e3:.2f}ms", f"{err:.1e}", f"{vmem / 1e3:.0f}KB", "SSD blocks"]


def main(argv=None) -> int:
    setup_compile_cache()
    rows = [bench_paged_decode(), bench_flash_prefill(), bench_rwkv6(), bench_mamba2()]
    print("=== Pallas kernels: ref wall-time (this host), interpret-mode max|Δ| vs oracle, VMEM/block ===")
    print_table(["kernel", "ref ms", "pallas max err", "VMEM/block", "tiling"], rows)
    save_json("kernels.json", {r[0]: r[1:] for r in rows})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
