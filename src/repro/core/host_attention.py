"""Host-side paged decode attention — the TPU-host analogue of NEO's PACPU
(ISPC) CPU kernel (§4 "Efficient CPU Kernels").

The paper's kernel properties we preserve:

* **paged KV** (vLLM-style block tables) to avoid fragmentation;
* **flash-decoding split** (Dao et al.): the KV sequence of each request is
  partitioned into page-granular blocks; every (row, block) pair is one task
  on the worker threads, and each row's partial softmax results are merged
  with the standard (m, l, acc) log-sum-exp combine in a fixed block order;
* **bandwidth-first layout**: the host pool keeps the device's 16-bit bits,
  a task gathers its pages with one ``np.take`` into per-thread scratch and
  widens them to float32 there, so DRAM streams 2 bytes per element once;
* **GQA aware**: scores and outputs are per-KV-head batched ``np.matmul``
  (BLAS) over the head's query group.

Every step of a task (gather, integer widen, BLAS, ``exp``) is a numpy loop
that releases the GIL, so tasks overlap on the worker threads. A row's
output depends only on its own blocks, so it is bitwise the same for any
thread count and any other rows in the call.

On a real TPU VM this module runs on the host cores next to the accelerator
(the engine calls it through an ordered ``io_callback`` from inside the jitted
decode step); in this container it is the literal execution path.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.config import ArchConfig

_BF16 = np.dtype(jnp.bfloat16)


def widen(bits: np.ndarray, out: np.ndarray) -> np.ndarray:
    """Write the KV values ``bits`` into the float32 array ``out``; returns it.

    ``bits`` is the uint16 view of a bfloat16 array: a bf16 value is the
    upper half of the float32 of the same value, so the widen is an exact
    integer shift in one numpy loop. Any other dtype is cast.
    """
    if bits.dtype == np.uint16:
        np.left_shift(bits, np.uint32(16), out=out.view(np.uint32),
                      dtype=np.uint32)
    else:
        np.copyto(out, bits)
    return out


def _merge_partials(parts: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]]):
    """Combine flash partials [(acc [H,hd], l [H], m [H]), ...] -> out [H,hd]."""
    m = np.max(np.stack([p[2] for p in parts]), axis=0)  # [H]
    num = np.zeros_like(parts[0][0])
    den = np.zeros_like(parts[0][1])
    for acc, l, mp in parts:
        corr = np.exp(mp - m)  # [H]
        num += acc * corr[:, None]
        den += l * corr
    return num / np.maximum(den, 1e-30)[:, None]


class HostAttention:
    """Paged decode attention over the host KV pool.

    ``pool_k`` / ``pool_v``: numpy, shape [L, P, page, KV, hd] (the
    ``PagePool(backend="host")`` arrays, or a kv-head slice of them under
    TP): bfloat16 for 16-bit architectures, float32 otherwise. Reads widen
    bf16 to float32 in per-thread scratch; a float32 pool is read as it is.

    Decode work is split into one task per (row, block of at most
    ``split_pages`` pages), dispatched over ``threads`` workers.
    """

    def __init__(self, cfg: ArchConfig, pool_k: np.ndarray, pool_v: np.ndarray,
                 threads: int = 1, split_pages: int = 64):
        self.cfg = cfg
        self.pool_k = pool_k
        self.pool_v = pool_v
        self.page = pool_k.shape[2]
        self.threads = max(1, threads)
        self.split_pages = split_pages  # flash-decoding task granularity
        self._tp: Optional[ThreadPoolExecutor] = (
            ThreadPoolExecutor(max_workers=self.threads) if self.threads > 1 else None
        )
        # what the tasks gather: the bf16 pool's raw bits (widened by
        # ``widen``), else the pool itself
        bf16 = pool_k.dtype == _BF16
        self._src_k = pool_k.view(np.uint16) if bf16 else pool_k
        self._src_v = pool_v.view(np.uint16) if bf16 else pool_v
        # per-thread gather/widen buffers, reused across calls (lane threads
        # and pool workers each get their own on first use)
        self._scratch = threading.local()
        # instrumentation (perf-model calibration + paper §5.5 bandwidth study)
        # — lock-protected: batch-0's io_callback and the batch-1 lane may
        # run concurrently from different threads
        self.busy_time = 0.0
        self.bytes_read = 0
        # zero-copy host-serving prefix gathers (suffix prefill over an
        # in-place host-resident prefix) — kept SEPARATE from busy_time so
        # the perf model's cpu_attn EWMA calibration only sees decode
        # attention; this pair backs PerfModel.t_host_prefix instead
        self.prefix_busy_time = 0.0
        self.prefix_bytes_read = 0
        self._acct_lock = threading.Lock()

    # ------------------------------------------------------------------
    def _buffers(self) -> Tuple[np.ndarray, ...]:
        """This thread's (gather k, gather v, wide k, wide v) scratch, each
        [split_pages, page, KV, hd]; the gather pair is None for a float32
        pool, which is gathered straight into the wide pair."""
        bufs = getattr(self._scratch, "bufs", None)
        if bufs is None:
            shape = (self.split_pages,) + self.pool_k.shape[2:]
            raw = self._src_k.dtype != np.float32
            bufs = (np.empty(shape, self._src_k.dtype) if raw else None,
                    np.empty(shape, self._src_v.dtype) if raw else None,
                    np.empty(shape, np.float32), np.empty(shape, np.float32))
            self._scratch.bufs = bufs
        return bufs

    @staticmethod
    def _gather(src: np.ndarray, ids: np.ndarray, raw: Optional[np.ndarray],
                wide: np.ndarray) -> np.ndarray:
        """Pages ``ids`` of one layer's ``src`` as float32 [n, page, KV, hd].

        ``mode="clip"`` lets ``np.take`` write straight into the scratch (the
        default mode copies ``out`` first, to leave it intact on an index
        error); page ids come from the engine's own tables."""
        n = len(ids)
        if raw is None:
            return np.take(src, ids, axis=0, out=wide[:n], mode="clip")
        return widen(np.take(src, ids, axis=0, out=raw[:n], mode="clip"),
                     wide[:n])

    def _block(self, layer: int, qg: np.ndarray, ids: np.ndarray, lo: int,
               hi: int, start_tok: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Flash partial of one row's queries ``qg`` [KV, qpk, hd] over its
        tokens [lo, hi), held by pages ``ids``; tokens before ``start_tok``
        are masked. Returns (acc [H, hd], l [H], m [H])."""
        KV, qpk, hd = qg.shape
        gk, gv, wk, wv = self._buffers()
        k = self._gather(self._src_k[layer], ids, gk, wk)
        v = self._gather(self._src_v[layer], ids, gv, wv)
        k = k.reshape(-1, KV, hd)[: hi - lo]  # [T, KV, hd]
        v = v.reshape(-1, KV, hd)[: hi - lo]
        s = np.matmul(qg, k.transpose(1, 2, 0))  # [KV, qpk, T]
        s *= 1.0 / np.sqrt(hd)
        if lo < start_tok:
            s[:, :, : start_tok - lo] = -np.inf
        m = np.max(s, axis=-1)  # [KV, qpk]
        np.subtract(s, m[..., None], out=s)
        np.exp(s, out=s)
        l = np.sum(s, axis=-1)
        acc = np.matmul(s, v.transpose(1, 0, 2))  # [KV, qpk, hd]
        H = KV * qpk
        return acc.reshape(H, hd), l.reshape(H), m.reshape(H)

    def _attend_rows(self, layer: int, q: np.ndarray, tables: np.ndarray,
                     n_tokens: Sequence[int], window: int) -> np.ndarray:
        """q [R, H, hd] float32; row i attends over its first ``n_tokens[i]``
        cached tokens (the last ``window`` of them when set) -> [R, H, hd]."""
        R, H, hd = q.shape
        KV = self.pool_k.shape[3]
        page, split = self.page, self.split_pages
        qg = q.reshape(R, KV, H // KV, hd)
        tasks: List[Tuple[int, int, int, int, int]] = []
        tokens = 0
        for i in range(R):
            n = int(n_tokens[i])
            start = n - window if window and n > window else 0
            n_pages = -(-n // page)
            for p0 in range(start // page, n_pages, split):
                p1 = min(p0 + split, n_pages)
                tasks.append((i, p0, p1, start, n))
            tokens += n - (start // page) * page
        with self._acct_lock:
            self.bytes_read += (2 * tokens * KV * hd
                                * self.pool_k.dtype.itemsize)

        def run(task: Tuple[int, int, int, int, int]):
            i, p0, p1, start, n = task
            return self._block(layer, qg[i], tables[i][p0:p1], p0 * page,
                               min(p1 * page, n), start)

        if self._tp is not None and len(tasks) > 1:
            parts = list(self._tp.map(run, tasks))
        else:
            parts = [run(t) for t in tasks]
        by_row: List[list] = [[] for _ in range(R)]
        for task, part in zip(tasks, parts):  # block order within each row
            by_row[task[0]].append(part)
        out = np.zeros((R, H, hd), np.float32)
        for i, row_parts in enumerate(by_row):
            if row_parts:
                out[i] = _merge_partials(row_parts)
        return out

    # ------------------------------------------------------------------
    def append_tokens(self, layer: int, rows: np.ndarray, k_new: np.ndarray,
                      v_new: np.ndarray, page_ids: np.ndarray, offsets: np.ndarray) -> None:
        """Write one new KV token per (host) row into the host pool, at the
        pool's dtype (a bf16 pool stores the bits the device pool stores
        for a device row)."""
        if len(rows) == 0:
            return
        self.pool_k[layer, page_ids, offsets] = k_new[rows]
        self.pool_v[layer, page_ids, offsets] = v_new[rows]

    def run_layer(
        self,
        layer: int,
        q: np.ndarray,  # [D, H, hd] — all rows; we compute host rows only
        k_new: np.ndarray,  # [D, KV, hd]
        v_new: np.ndarray,
        *,
        host_rows: np.ndarray,  # [R] int indices into D
        tables: np.ndarray,  # [R, MP] page ids in the HOST pool
        lens: np.ndarray,  # [R] tokens valid BEFORE the append
        page_ids: np.ndarray,  # [R] page for the new token
        offsets: np.ndarray,  # [R]
        window: int = 0,
    ) -> np.ndarray:
        """Append new KV for host rows and attend; returns [D, H, hd] float32
        with zeros in non-host rows."""
        D, H, hd = q.shape
        out = np.zeros((D, H, hd), np.float32)
        if len(host_rows) == 0:
            return out
        t0 = time.perf_counter()
        self.append_tokens(layer, host_rows, k_new, v_new, page_ids, offsets)
        out[host_rows] = self._attend_rows(
            layer, q[host_rows].astype(np.float32), tables,
            np.asarray(lens) + 1, window)
        with self._acct_lock:
            self.busy_time += time.perf_counter() - t0
        return out

    # ------------------------------------------------------------------
    # zero-copy host-serving: prefix partials for the suffix-prefill path
    # ------------------------------------------------------------------
    def prefix_partials(
        self,
        layer: int,
        q: np.ndarray,  # [B, S, H, hd] — suffix queries (padded rows ok)
        tables: np.ndarray,  # [B, MP] page ids in the HOST pool
        prefix_lens: np.ndarray,  # [B] valid cached-prefix tokens per row
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Flash partials of suffix queries over host-RESIDENT prefix pages.

        The pages are read IN PLACE at their absolute positions — the cached
        prefix never crosses PCIe; only the (small) partials return to the
        device, where :func:`attn_lib.suffix_attention_merge` combines them
        with the causal suffix scores.  Rows with ``prefix_lens == 0``
        return ``m = -1e30`` so the merge discards them.  Returns
        ``(acc [B,S,H,hd], l [B,S,H], m [B,S,H])`` float32.
        """
        B, S, H, hd = q.shape
        KV = self.pool_k.shape[3]
        qpk = H // KV
        scale = 1.0 / np.sqrt(hd)
        acc = np.zeros((B, S, H, hd), np.float32)
        l = np.zeros((B, S, H), np.float32)
        m = np.full((B, S, H), -1e30, np.float32)
        t0 = time.perf_counter()
        for b in range(B):
            T = int(prefix_lens[b])
            if T <= 0:
                continue
            npg = -(-T // self.page)
            ids = tables[b, :npg]
            k_src = self._src_k[layer, ids].reshape(-1, KV, hd)[:T]
            v_src = self._src_v[layer, ids].reshape(-1, KV, hd)[:T]
            with self._acct_lock:
                # DRAM bytes at the POOL's dtype (bf16 on 16-bit archs),
                # before the f32 widen — same convention as the decode
                # path's bytes_read
                self.prefix_bytes_read += k_src.nbytes + v_src.nbytes
            k = widen(k_src, np.empty(k_src.shape, np.float32))
            v = widen(v_src, np.empty(v_src.shape, np.float32))
            qg = q[b].astype(np.float32).reshape(S, KV, qpk, hd)
            s = np.einsum("skqd,tkd->skqt", qg, k, optimize=True) * scale
            mb = np.max(s, axis=-1)  # [S, KV, qpk]
            e = np.exp(s - mb[..., None])
            lb = np.sum(e, axis=-1)
            ab = np.einsum("skqt,tkd->skqd", e, v, optimize=True)
            acc[b] = ab.reshape(S, H, hd)
            l[b] = lb.reshape(S, H)
            m[b] = mb.reshape(S, H)
        with self._acct_lock:
            self.prefix_busy_time += time.perf_counter() - t0
        return acc, l, m

    # -- standalone oracle-checkable entry (tests) ----------------------------
    def attend(self, layer: int, q: np.ndarray, tables: np.ndarray,
               n_tokens: np.ndarray, window: int = 0) -> np.ndarray:
        """Pure attention (no append): q [R,H,hd] -> [R,H,hd]."""
        return self._attend_rows(layer, q.astype(np.float32), tables,
                                 n_tokens, window)
