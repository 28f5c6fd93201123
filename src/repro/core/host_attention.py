"""Host-side paged decode attention — the TPU-host analogue of NEO's PACPU
(ISPC) CPU kernel (§4 "Efficient CPU Kernels").

The paper's kernel properties we preserve:

* **paged KV** (vLLM-style block tables) to avoid fragmentation;
* **flash-decoding split** (Dao et al.): the KV sequence of each request is
  partitioned into page-granular blocks; every (row, block) pair is one task
  on the worker threads, and each row's partial softmax results are merged
  with the standard (m, l, acc) log-sum-exp combine in a fixed block order;
* **bandwidth-first layout**: the host pool keeps the device's 16-bit bits,
  and a task widens them to float32 as it reads them, so DRAM streams 2
  bytes per element once;
* **GQA aware**: each K and V row is read once for its whole query group.

A task is one call of the native kernel (``host_attention.c``, built on
first use with the system C compiler and called through ``ctypes``, which
releases the GIL): it walks the block's pages once for K and once for V,
widening each row in registers, with no BLAS and no scratch copy. Where no
compiler is found or the build fails, the task is the numpy fallback
(``np.take`` into per-thread scratch, an integer widen, two per-KV-head
``np.matmul``). ``HostAttention.kernel`` says which one runs. A row's output
depends only on its own blocks, so it is bitwise the same for any thread
count and any other rows in the call.

On a real TPU VM this module runs on the host cores next to the accelerator
(the engine calls it through an ordered ``io_callback`` from inside the jitted
decode step); in this container it is the literal execution path.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import logging
import os
import platform
import subprocess
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.config import ArchConfig

_BF16 = np.dtype(jnp.bfloat16)
_log = logging.getLogger(__name__)

# The native kernel's source and the command that builds it. No -ffast-math:
# its start-up code sets flush-to-zero for the whole process.
_SRC = Path(__file__).with_suffix(".c")
_CC = ("cc", "-O3", "-march=native", "-fno-math-errno", "-shared", "-fPIC")
_P, _I = ctypes.c_void_p, ctypes.c_int64
_RUN_ARGS = (
    [_P, _P] + [_I] * 6  # k, v; k and v (page, token, head) element strides
    + [ctypes.c_int] + [_I] * 4 + [ctypes.c_float]  # bf16, page, kv, g, hd, scale
    + [_P, _P, _I]  # q, tables, tables' row length
    + [_P, _I, _P, _P, _P, _P]  # tasks, their count, first, order, next, remaining
    + [_P] * 4)  # acc, l, m, out


def _cpu_model() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            return next((ln for ln in f if ln.startswith("model name")), "")
    except OSError:
        return ""


def native_library() -> Optional[ctypes.CDLL]:
    """The native kernel for this machine, built on first use; None where
    it cannot be built or loaded."""
    return _build(_CC)


@functools.lru_cache(maxsize=None)
def _build(cc: Tuple[str, ...]) -> Optional[ctypes.CDLL]:
    """Compile ``_SRC`` with ``cc`` into the user's cache directory, keyed by
    the source, the command and the CPU (``-march=native``), and load it.

    The library is installed by an atomic rename, so processes that build at
    once each load a whole file."""
    src = _SRC.read_bytes()
    key = hashlib.sha256(b"\0".join(
        [src, " ".join(cc).encode(), platform.machine().encode(),
         _cpu_model().encode()])).hexdigest()[:16]
    try:
        cache = Path.home() / ".cache" / "repro"
        lib = cache / f"host_attention-{key}.so"
        if not lib.exists():
            cache.mkdir(parents=True, exist_ok=True)
            with tempfile.TemporaryDirectory(dir=cache) as tmp:
                out = Path(tmp) / lib.name
                subprocess.run([*cc, "-o", str(out), str(_SRC)], check=True,
                               capture_output=True, timeout=300)
                os.replace(out, lib)
        so = ctypes.CDLL(str(lib))
    except (OSError, RuntimeError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", None) or b""
        _log.warning("host attention: no native kernel (%s %s); using numpy",
                     e, detail.decode(errors="replace")[-2000:])
        return None
    so.hattn_run.argtypes = _RUN_ARGS
    so.hattn_run.restype = ctypes.c_int
    return so


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


def _merge_partials(acc: np.ndarray, l: np.ndarray, m: np.ndarray,
                    first: np.ndarray) -> np.ndarray:
    """Combine flash partials acc [N, H, hd], l [N, H], m [N, H] of N tasks
    into [R, H, hd]: row i owns tasks [first[i], first[i + 1]), in block
    order, and every row owns at least one."""
    counts = np.diff(first)
    starts = first[:-1]
    mx = np.maximum.reduceat(m, starts, axis=0)  # [R, H]
    corr = np.exp(m - np.repeat(mx, counts, axis=0))  # [N, H]
    num = np.add.reduceat(acc * corr[..., None], starts, axis=0)
    den = np.add.reduceat(l * corr, starts, axis=0)
    return num / np.maximum(den, 1e-30)[..., None]


class HostAttention:
    """Paged decode attention over the host KV pool.

    ``pool_k`` / ``pool_v``: numpy, shape [L, P, page, KV, hd] (the
    ``PagePool(backend="host")`` arrays, or a kv-head slice of them under
    TP): bfloat16 for 16-bit architectures, float32 otherwise. Reads widen
    bf16 to float32; a float32 pool is read as it is.

    Decode work is split into one task per (row, block of at most
    ``split_pages`` pages), run on ``threads`` workers. The task kernel is
    chosen once, here, from what the machine and the pool offer:
    ``"native"`` where the C kernel builds and the pool is bf16 or float32
    with contiguous head rows of a multiple of 16 elements, else ``"numpy"``.
    The native workers take tasks off a shared counter, largest first, and
    whoever finishes a row's last block merges the row; the numpy tasks go
    through the pool one by one and the rows merge in one numpy pass.
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
        self._lib = None
        if ((bf16 or pool_k.dtype == np.float32) and pool_v.dtype == pool_k.dtype
                and pool_k.shape == pool_v.shape and pool_k.shape[4] % 16 == 0
                and pool_k.strides[4] == pool_v.strides[4] == pool_k.itemsize):
            self._lib = native_library()
        # element strides of a page, a token and a kv head, K's then V's
        self._strides = tuple(n // pool_k.itemsize for n in pool_k.strides[1:4]
                              + pool_v.strides[1:4])
        self.kernel = "numpy" if self._lib is None else "native"
        self.native_calls = 0  # _attend_rows calls the native kernel served
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
               hi: int, start_tok: int, acc: np.ndarray, l: np.ndarray,
               m: np.ndarray) -> None:
        """numpy flash partial of one row's queries ``qg`` [KV, qpk, hd] over
        its tokens [lo, hi), held by pages ``ids``; tokens before
        ``start_tok`` are masked. Writes acc [H, hd], l [H] and m [H]."""
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
        mb = np.max(s, axis=-1)  # [KV, qpk]
        np.subtract(s, mb[..., None], out=s)
        np.exp(s, out=s)
        H = KV * qpk
        l[:] = np.sum(s, axis=-1).reshape(H)
        m[:] = mb.reshape(H)
        acc[:] = np.matmul(s, v.transpose(1, 0, 2)).reshape(H, hd)

    def _attend_rows(self, layer: int, q: np.ndarray, tables: np.ndarray,
                     n_tokens: Sequence[int], window: int) -> np.ndarray:
        """q [R, H, hd] float32; row i attends over its first ``n_tokens[i]``
        cached tokens (the last ``window`` of them when set) -> [R, H, hd]."""
        R, H, hd = q.shape
        P, KV = self.pool_k.shape[1], self.pool_k.shape[3]
        if H % KV or hd != self.pool_k.shape[4]:
            raise ValueError(f"q of {H} heads x {hd} does not fit a pool of "
                             f"{KV} kv heads x {self.pool_k.shape[4]}")
        page, split = self.page, self.split_pages
        tasks: List[Tuple[int, int, int, int, int]] = []  # row, p0, lo, hi, start
        first = [0]  # row i owns tasks [first[i], first[i + 1])
        tokens = 0
        for i in range(R):
            n = int(n_tokens[i])
            start = n - window if window and n > window else 0
            n_pages = -(-n // page)
            for p0 in range(start // page, n_pages, split):
                tasks.append((i, p0, p0 * page, min((p0 + split) * page, n),
                              start))
            first.append(len(tasks))
            tokens += n - (start // page) * page
        with self._acct_lock:
            self.bytes_read += (2 * tokens * KV * hd
                                * self.pool_k.dtype.itemsize)
        out = np.zeros((R, H, hd), np.float32)
        if not tasks:
            return out
        if tables.shape[1] < -(-max(int(n) for n in n_tokens) // page):
            raise ValueError(f"tables of {tables.shape[1]} pages do not hold "
                             f"{max(n_tokens)} tokens")
        # page ids as np.take(mode="clip") reads them
        tab = np.clip(tables, 0, P - 1).astype(np.int64)
        N = len(tasks)
        parts = (np.empty((N, H, hd), np.float32),  # acc, l, m of each task
                 np.empty((N, H), np.float32), np.empty((N, H), np.float32))
        first = np.asarray(first, np.int64)
        if self._lib is not None:
            self._run_native(layer, q, tab, np.asarray(tasks, np.int64), first,
                             parts, out)
        else:
            self._run_numpy(layer, q, tab, tasks, parts)
            rows = np.flatnonzero(np.diff(first))
            out[rows] = _merge_partials(*parts, first[np.append(rows, R)])
        return out

    def _workers(self, n_tasks: int) -> int:
        return min(self.threads, n_tasks) if self._tp is not None else 1

    def _run_numpy(self, layer: int, q: np.ndarray, tab: np.ndarray,
                   tasks: List[Tuple[int, int, int, int, int]],
                   parts: Tuple[np.ndarray, ...]) -> None:
        """Each task's partial into ``parts``, one pool job per task."""
        R, H, hd = q.shape
        qg = q.reshape(R, self.pool_k.shape[3], -1, hd)
        acc, l, m = parts

        def run(j: int) -> None:
            i, p0, lo, hi, start = tasks[j]
            self._block(layer, qg[i], tab[i, p0:-(-hi // self.page)], lo, hi,
                        start, acc[j], l[j], m[j])

        if self._workers(len(tasks)) > 1:
            for _ in self._tp.map(run, range(len(tasks))):
                pass
        else:
            for j in range(len(tasks)):
                run(j)

    def _run_native(self, layer: int, q: np.ndarray, tab: np.ndarray,
                    tasks: np.ndarray, first: np.ndarray,
                    parts: Tuple[np.ndarray, ...], out: np.ndarray) -> None:
        """All tasks and row merges in the C kernel: one ``hattn_run`` call
        per worker, each taking tasks off a shared counter, largest first."""
        R, H, hd = q.shape
        KV = self.pool_k.shape[3]
        q = np.ascontiguousarray(q, np.float32)
        order = np.argsort(tasks[:, 2] - tasks[:, 3], kind="stable")
        counters = np.zeros(1 + R, np.int64)  # next task, then each row's
        counters[1:] = np.diff(first)  # unfinished tasks
        pk, pv = self._src_k, self._src_v
        args = (pk.ctypes.data + layer * pk.strides[0],
                pv.ctypes.data + layer * pv.strides[0], *self._strides,
                int(pk.dtype == np.uint16), self.page, KV, H // KV, hd,
                1.0 / np.sqrt(hd), q.ctypes.data, tab.ctypes.data,
                tab.shape[1], tasks.ctypes.data, len(tasks), first.ctypes.data,
                order.ctypes.data, counters.ctypes.data,
                counters.ctypes.data + counters.itemsize,
                *(a.ctypes.data for a in parts), out.ctypes.data)
        workers = self._workers(len(tasks))
        if workers > 1:
            rcs = list(self._tp.map(lambda _: self._lib.hattn_run(*args),
                                    range(workers)))
        else:
            rcs = [self._lib.hattn_run(*args)]
        if any(rcs):
            raise RuntimeError(f"host attention kernel failed: {rcs}")
        with self._acct_lock:
            self.native_calls += 1

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
