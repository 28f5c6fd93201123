"""Set-up work that compiles every shape a cell's traffic can reach, so that
nothing compiles inside the measured window.

The served path compiles per shape:

- the fused decode graph per (rows bucket D, pages bucket MP) and one host
  lane graph per (lane, D); these carry host callbacks, which JAX does not
  persist, so every run compiles them again (compiled ahead of time here, in
  parallel threads, after the eager work);
- the prefill graph per (requests B, length bucket S), persisted after the
  first run;
- the slice of a decode step's logits per (D, rows);
- eager page copies per page count n: the gather of a swap-out, the scatter
  of a prefill or swap-in (``DevicePool.put_pages``), and the pad, slice and
  reshape of a prefill's KV.  These are replayed here on the scratch page,
  with the same calls on arrays of the same shapes.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import loadgen


def _bucket(n: int, minimum: int) -> int:
    """The program's power-of-two bucket (``executor._bucket``)."""
    b = minimum
    while b < n:
        b *= 2
    return b


def buckets(lo: int, hi: int, minimum: int) -> List[int]:
    """Every bucket from that of ``lo`` to that of ``hi``."""
    b, out = _bucket(lo, minimum), []
    while b <= _bucket(hi, minimum):
        out.append(b)
        b *= 2
    return out


def prefill_batches(mix: Dict, S: int, budget: int) -> int:
    """The most requests one prefill of bucket ``S`` can hold under this
    mix: the scheduler packs consecutive requests of its queue while their
    tokens fit ``budget``, and a run seed only permutes the mix's sizes
    within blocks of ``permute_block``, so consecutive requests come from two
    adjacent blocks.  The longest of the batch (a first prompt or a replay
    after preemption) is over ``S / 2``; the others are the shortest prompts
    of the two blocks."""
    prompts = loadgen.sizes(mix, loadgen.request_count(mix, 0))[0]
    block = int(mix["permute_block"])
    room = budget - (S // 2 + 1)
    most = 1
    for lo in range(0, len(prompts), block):
        n, used = 1, 0
        for x in np.sort(prompts[lo:lo + 2 * block]):
            if x > S or used + x > room:
                break
            n, used = n + 1, used + x
        most = max(most, n)
    return most


def shapes(engine, mix: Dict) -> Dict:
    """The shapes this cell's traffic can reach."""
    ecfg = engine.engine_cfg
    page = engine.pool.page_size
    max_tokens = mix["prompt"]["max"] + mix["output"]["max"]
    max_pages = -(-max_tokens // page)
    S = buckets(mix["prompt"]["min"], max_tokens - 1, 16)
    budget = ecfg.max_batch_tokens
    prefill = [(B, s) for s in S
               for B in range(1, min(prefill_batches(mix, s, budget), ecfg.max_requests) + 1)]
    return {
        "D": buckets(8, ecfg.max_requests, 8),
        "MP": buckets(4, max_pages, 4),
        "S": S,
        "prefill": prefill,
        "lanes": list(range(1, ecfg.max_host_lanes + 1)),
        "pages": max_pages,
    }


def graph_jobs(engine, sh: Dict) -> List:
    """(jitted function, example arguments) of every decode, lane and
    prefill graph; compiling them ahead of time fills the functions' caches."""
    ex, dev = engine.executor, engine.pool.device
    params = ex.params
    i32 = np.int32
    jobs = []
    for D in sh["D"]:
        for MP in sh["MP"]:
            args = (params, np.zeros(D, i32), np.zeros(D, i32),
                    np.zeros((D, MP), i32), np.zeros(D, i32),
                    np.ones(D, bool), np.zeros(D, i32), np.zeros(D, i32),
                    dev.k, dev.v)
            jobs.append((ex.decode_fn(D, MP), args))
        for lane in sh["lanes"]:
            jobs.append((ex.decode_lane_fn(lane),
                         (params, np.zeros(D, i32), np.zeros(D, i32))))
    for B, S in sh["prefill"]:
        jobs.append((ex.prefill_fn(B, S),
                     (params, np.zeros((B, S), i32), np.ones(B, i32), {})))
    return jobs


def _pool_free_work(engine, sh: Dict, n: int) -> None:
    """The per-page-count eager work that leaves the pool alone, for a worker
    thread: a prefill's pad or slice and reshape, a swap-in's float16
    upload, the index arithmetic of the pool scatter (on a one-element-wide
    stand-in of the pool: it compiles per index shape, not per pool)."""
    dev = engine.pool.device
    L, P, page, KV, hd = dev.k.shape
    dt = dev.k.dtype
    s_pad = n * page
    # a prefill of n pages runs in any bucket from its own length's up
    for s in sh["S"]:
        if s < _bucket(s_pad - page + 1, 16):
            continue
        kr = jnp.zeros((L, s, KV, hd), dt)
        if s_pad > s:
            kr = jnp.pad(kr, [(0, 0), (0, s_pad - s), (0, 0), (0, 0)])
        else:
            kr = kr[:, :s_pad]
        jax.block_until_ready(kr.reshape(kr.shape[0], n, page, *kr.shape[2:]))
    # a zero-stride view shares the conversion's compile with a real copy
    staged = np.broadcast_to(np.float16(0), (L, n, page, KV, hd))
    jax.block_until_ready(jnp.asarray(staged, dt))
    idx = np.asarray([0] * n, np.int32)
    stand_in = jnp.zeros((1, P, 1, 1, 1), dt)
    jax.block_until_ready(stand_in.at[:, idx].set(jnp.zeros((1, n, 1, 1, 1), dt)))


def _logit_slices(engine, D: int) -> None:
    """A decode step hands the first n rows of its D-row logits to the host."""
    logits = jnp.zeros((D, engine.cfg.vocab_size), jnp.float32)
    for n in range(1, D + 1):
        jax.block_until_ready(logits[:n])


def replay_page_copies(engine, sh: Dict) -> None:
    """The eager work that reads or writes the device pool, on this thread,
    one pool copy at a time as in the engine: the scatter of a prefill or
    swap-in and the gather of a swap-out, per page count."""
    dev = engine.pool.device
    L, _, page, KV, hd = dev.k.shape
    scratch = engine._scratch[0]
    for n in range(1, sh["pages"] + 1):
        pages = [scratch] * n
        upd = jnp.zeros((L, n, page, KV, hd), dev.k.dtype)
        dev.put_pages(pages, upd, upd)
        del upd
        jax.block_until_ready(dev.k[:, np.asarray(pages, np.int32)])
    jax.block_until_ready((dev.k, dev.v))


def replay_pool_free(engine, sh: Dict, threads: int) -> None:
    """The eager work that leaves the pool alone, on worker threads."""
    dev = engine.pool.device
    L, _, _, KV, hd = dev.k.shape
    # prefill: the KV of request i is k_all[:, i], per (B, S)
    for B, S in sh["prefill"]:
        k_all = jnp.zeros((L, B, S, KV, hd), dev.k.dtype)
        jax.block_until_ready(k_all[:, B - 1])
        del k_all
    with ThreadPoolExecutor(max_workers=threads) as pool:
        futures = [pool.submit(_logit_slices, engine, D) for D in sh["D"]]
        futures += [pool.submit(_pool_free_work, engine, sh, n)
                    for n in range(1, sh["pages"] + 1)]
        for f in futures:
            f.result()


def warm(engine, mix: Dict, log=lambda msg: None) -> Dict:
    """Replays the eager work, first what copies the pool, alone, then the
    rest on threads; then compiles the graphs on threads.

    The graphs come last because JAX keeps lowered and compiled programs in
    least-recently-used caches of 2,048 entries, shared with the eager
    operations, which the replays fill twice over: a graph compiled before
    them is evicted and compiles again inside the window."""
    sh = shapes(engine, mix)
    threads = max(2, min(8, (os.cpu_count() or 2) - 2))
    replay_page_copies(engine, sh)
    log("page copies replayed")
    replay_pool_free(engine, sh, threads)
    log("pool-free eager work replayed")
    jobs = graph_jobs(engine, sh)
    with ThreadPoolExecutor(max_workers=threads) as graphs:
        list(graphs.map(lambda j: j[0].lower(*j[1]).compile(), jobs))
    log("graphs compiled")
    return {"graphs": len(jobs), **sh}
