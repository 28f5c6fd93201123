"""The host's memory ceiling beside host attention's speed on it.

Measures, on the machine it runs on, (1) the read and copy bandwidth of
``--threads`` threads over a buffer far larger than the caches (numpy calls
that release the GIL), and (2) ``HostAttention.attend`` alone, native and
numpy kernels, at the shapes of the benchmark's cells (bf16 pool, page 16,
head_dim 128, rows of ``--tokens`` tokens on shuffled pages). It prints one
JSON line: GB/s of each, each kernel's share of the read ceiling, the
native kernel's largest distance from the numpy one, and, for each value
given with ``--gbps``, that reading's share of the ceiling.

    JAX_PLATFORMS=cpu PYTHONPATH=src python experiments/host_attention_ceiling.py \\
        --threads 8 --gbps 2.3176 3.8578

No benchmark cell runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import time
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import host_attention
from repro.core.host_attention import HostAttention

SHAPES = {"qwen3-0.6b": (8, 16), "yi-9b-24L": (4, 32)}  # (KV heads, q heads)


def bandwidth(threads: int, gib: float, reps: int) -> dict:
    """Best-of-``reps`` read and copy GB/s of ``threads`` threads, each over
    its own slice of one ``gib``-GiB buffer."""
    src = np.ones(int(gib * 2**30) // 8, np.uint64)
    dst = np.empty_like(src)
    parts = np.array_split(np.arange(src.size), threads)
    spans = [(p[0], p[-1] + 1) for p in parts]
    out = {}
    with ThreadPoolExecutor(threads) as tp:
        for name, fn, moved in (
                ("read_gbps", lambda a, b: np.bitwise_xor.reduce(src[a:b]), 1),
                ("copy_gbps", lambda a, b: np.copyto(dst[a:b], src[a:b]), 2)):
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                list(tp.map(lambda s: fn(*s), spans))
                best = min(best, time.perf_counter() - t0)
            out[name] = moved * src.nbytes / best / 1e9
    return out


def kernel_gbps(kv: int, heads: int, rows: int, tokens: int, threads: int,
                reps: int, seed: int) -> dict:
    """GB/s of ``attend`` over ``rows`` rows of ``tokens`` tokens, for each
    kernel this machine can run, and the native kernel's largest distance
    from the numpy one."""
    rng = np.random.default_rng(seed)
    cfg = get_smoke_config("qwen3-0.6b")
    page, hd = 16, 128
    per_row = -(-tokens // page)
    P = rows * per_row
    shape = (1, P, page, kv, hd)
    pk = rng.standard_normal(shape, np.float32).astype(jnp.bfloat16)
    pv = rng.standard_normal(shape, np.float32).astype(jnp.bfloat16)
    tables = rng.permutation(P).reshape(rows, per_row).astype(np.int32)
    lens = np.full(rows, tokens, np.int32)
    q = rng.standard_normal((rows, heads, hd), np.float32)
    got, outs = {}, {}
    native = host_attention.native_library
    for kernel in ("native", "numpy"):
        if kernel == "numpy":
            host_attention.native_library = lambda: None
        try:
            ha = HostAttention(cfg, pk, pv, threads=threads)
        finally:
            host_attention.native_library = native
        if ha.kernel != kernel:
            continue
        outs[kernel] = ha.attend(0, q, tables, lens)  # warm: threads, scratch
        best = float("inf")
        for _ in range(reps):
            b0, t0 = ha.bytes_read, time.perf_counter()
            ha.attend(0, q, tables, lens)
            best = min(best, time.perf_counter() - t0)
        got[kernel] = (ha.bytes_read - b0) / best / 1e9
    if len(outs) == 2:
        got["max_abs_diff"] = float(np.max(np.abs(outs["native"] - outs["numpy"])))
    return got


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--threads", type=int, default=8,
                    help="worker threads (the cells' host_threads)")
    ap.add_argument("--rows", type=int, default=30)
    ap.add_argument("--tokens", type=int, default=2200)
    ap.add_argument("--gib", type=float, default=2.0)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gbps", type=float, nargs="*", default=[],
                    help="host_attn_gbps readings to state as shares of the ceiling")
    args = ap.parse_args()
    try:
        cc = subprocess.run(["cc", "--version"], capture_output=True, text=True,
                            check=True).stdout.splitlines()[0]
    except (OSError, subprocess.SubprocessError):
        cc = None
    res = {"cpu": host_attention._cpu_model().split(":")[-1].strip(),
           "machine": platform.machine(), "cores": os.cpu_count(), "cc": cc,
           "threads": args.threads}
    res.update(bandwidth(args.threads, args.gib, args.reps))
    for name, (kv, heads) in SHAPES.items():
        k = kernel_gbps(kv, heads, args.rows, args.tokens, args.threads,
                        args.reps, args.seed)
        for kernel in ("native", "numpy"):
            if kernel in k:
                k[f"{kernel}_share"] = k[kernel] / res["read_gbps"]
        res[name] = k
    res["gbps_shares"] = {str(g): g / res["read_gbps"] for g in args.gbps}
    print(json.dumps(res))


if __name__ == "__main__":
    main()
