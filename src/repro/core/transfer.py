"""Asynchronous KV transfer engine (the PCIe DMA stage of Fig. 5).

NEO overlaps KV swaps with compute: the scheduler's no-bubble inequalities
budget ``T_swap`` under the device stages, and the execution layer must
actually run the copies concurrently for the plan to be realized.  This
module replaces :meth:`DualPool.swap_request`'s blocking whole-request copy
with a **launch → join** protocol:

* :meth:`TransferEngine.swap_out` / :meth:`swap_in` are called at *plan*
  time (the engine's LAUNCH phase).  They synchronously update the free-page
  accounting and the request's ``pages``/``location`` — so the scheduler's
  view stays identical to the serial path — and enqueue the actual data
  movement on a background worker.
* The returned :class:`TransferHandle` is joined immediately **before the
  pages are touched**, and joins are LANE-SCOPED: each host-lane dispatch
  thread joins only the swap-outs whose request it decodes
  (:meth:`join_requests`), and the engine joins swap-ins before the device
  decode graph consumes the pool.  Transfers nobody consumes this step join
  at the end-of-step :meth:`drain`.

Copies run on **per-direction streams** — one background worker per PCIe
direction (device→host and host→device), modelling the full-duplex DMA
engines of real hardware — so a swap-out burst never queues behind swap-ins
(or vice versa).  ``per_direction=False`` restores the single shared worker
(the PR-1 behavior) for A/B measurement; byte accounting is identical in
both modes.  Copies are page-granular and layer-wise (each worker streams
``[layer, pages]`` chunks), with per-job byte and wall-time accounting so
the engine can report measured PCIe bandwidth and how many bytes were
hidden under compute.

Under tensor parallelism (``shards > 1``) every request-swap fans out into
one job **per shard per direction** — each shard's worker moves that
shard's kv-head slice of the pages over its own stream (``out0``/``out1``/
``in0``/…), modelling the per-device PCIe links whose aggregate bandwidth
scales with the device count.  The kv-head slices partition the arrays, so
summed byte accounting is EXACTLY the single-shard total; the handle joins
all shards of a page (its event fires when the last shard job lands), and
``TransferHandle.hidden_bytes`` sums per-job hidden bytes so the engine's
counter reconciles span-for-span against the per-shard copy tracks.

Thread-safety contract:

* ``swap_out``/``swap_in`` and any ``join`` that applies a staged *device*
  write (i.e. joining swap-ins) must run on the engine thread — the device
  pool is a functionally-updated jax array and only the engine thread may
  reassign it.  Joining swap-outs is safe from any thread (host pool writes
  happen on the worker; the join only waits).
* Device reads are snapshotted at submit time: jax arrays are immutable, so
  the gather dispatched in ``swap_out`` stays valid even after the decode
  graph donates and replaces the pool buffers.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from repro.core.kv_cache import DualPool
from repro.core.request import Request


@dataclass
class TransferStats:
    """Aggregate accounting (lock-protected inside the engine)."""

    jobs: int = 0
    bytes_out: int = 0  # device -> host
    bytes_in: int = 0  # host -> device
    busy_time: float = 0.0  # summed worker wall time spent copying
    # per-stream copy time ("out" / "in"; one "all" key in single-worker
    # mode; "out0"/"in1"/… per shard under TP) — concurrent streams can
    # overlap, so their sum (== busy_time) may exceed the wall-clock window
    busy_by_stream: Dict[str, float] = field(default_factory=dict)
    # per-stream bytes moved — under TP this records the per-shard copy
    # split (each shard's kv-head slice of every swapped page)
    bytes_by_stream: Dict[str, int] = field(default_factory=dict)
    wait_time: float = 0.0  # time join() callers spent blocked

    @property
    def total_bytes(self) -> int:
        return self.bytes_out + self.bytes_in

    def bandwidth(self) -> float:
        """Measured copy bandwidth (bytes/s) over worker busy time."""
        if self.busy_time <= 0:
            return 0.0
        return self.total_bytes / self.busy_time


class TransferHandle:
    """Future for one queued request-swap; join before touching the pages.

    One handle spans every copy job of the swap — a single job normally,
    one per shard under TP (each moving its kv-head slice).  The event
    fires when the LAST job lands, so a join waits for all shards of a
    page; ``copy_start``/``copy_end`` bracket the union of the job windows.
    """

    def __init__(self, kind: str, req: Request, nbytes: int):
        self.kind = kind  # "out" | "in"
        self.req = req
        self.nbytes = nbytes  # total across all jobs
        # engine iteration that launched this swap (tracing: pairs the
        # worker's copy span with that iteration's dispatch window)
        self.trace_iter = 0
        self.error: Optional[BaseException] = None
        self._event = threading.Event()
        self._apply: Optional[Callable[[], None]] = None  # staged device write
        self._joined = False
        # copy window stamped by the worker — the engine intersects it with
        # its device-lane window to count bytes hidden under compute
        self.copy_start: float = 0.0
        self.copy_end: float = 0.0
        # multi-job bookkeeping (worker-side, under the engine's lock)
        self._jobs_total = 1
        self._jobs_done = 0
        self._job_spans: List[Tuple[int, float, float]] = []  # (nbytes, t0, t1)

    def hidden_fraction(self, window_start: float, window_end: float) -> float:
        """Fraction of this copy's wall time overlapped by [start, end]."""
        dur = self.copy_end - self.copy_start
        if dur <= 0:
            return 0.0
        ov = min(self.copy_end, window_end) - max(self.copy_start, window_start)
        return max(0.0, min(1.0, ov / dur))

    def hidden_bytes(self, window_start: float, window_end: float) -> int:
        """Bytes of this swap hidden under [start, end], summed per job.

        Computed span-by-span with the same ``int(nbytes * fraction)``
        truncation :mod:`repro.obs.reconcile` applies to each traced copy
        span — for a single-job handle this equals the legacy
        ``int(nbytes * hidden_fraction(...))`` exactly, and under TP the
        per-shard sum stays reconcilable where one whole-handle fraction
        would not.
        """
        total = 0
        for nb, t0, t1 in self._job_spans:
            dur = t1 - t0
            if dur <= 0:
                continue
            ov = min(t1, window_end) - max(t0, window_start)
            frac = max(0.0, min(1.0, ov / dur))
            total += int(nb * frac)
        return total

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)


@dataclass
class _Job:
    handle: TransferHandle
    fn: Callable[[], None]
    nbytes: int  # this job's share (== handle.nbytes for single-job swaps)


class TransferEngine:
    """Background copy streams executing page-granular, layer-wise KV moves.

    One worker per PCIe direction by default (``per_direction=True``);
    ``per_direction=False`` runs every job on a single shared worker — the
    legacy mode, kept for A/B measurement and the byte-accounting parity
    test.
    """

    def __init__(self, pool: DualPool, *, per_direction: bool = True,
                 shards: int = 1):
        self.pool = pool
        self.stats = TransferStats()
        self._lock = threading.Lock()
        # tracing (repro.obs): set by the engine when EngineConfig.tracing
        # is on; workers emit one copy span per job on their stream's track
        self.tracer = None
        self.trace_iter = 0
        self.per_direction = per_direction
        # TP: one stream (and worker) per shard per direction, each moving
        # its kv-head slice of the swapped pages — aggregate PCIe bandwidth
        # scales with the shard count while byte totals stay identical.
        self.shards = max(1, int(shards))
        kv_heads = pool.host.k.shape[3]
        if self.shards > 1 and kv_heads % self.shards != 0:
            raise ValueError(
                f"shards={self.shards} must divide the pool's "
                f"{kv_heads} kv head(s)")
        dirs = ("out", "in") if per_direction else ("all",)
        if self.shards == 1:
            streams = dirs
        else:
            streams = tuple(f"{d}{s}" for d in dirs for s in range(self.shards))
        self._queues: Dict[str, "queue.Queue[Optional[_Job]]"] = {
            s: queue.Queue() for s in streams
        }
        self._pending: List[TransferHandle] = []
        self._workers = {
            s: threading.Thread(target=self._run, args=(s,),
                                name=f"neo-transfer-{s}", daemon=True)
            for s in streams
        }
        for w in self._workers.values():
            w.start()
        self._closed = False

    def _stream(self, kind: str, shard: int = 0) -> str:
        d = kind if self.per_direction else "all"
        return d if self.shards == 1 else f"{d}{shard}"

    # ------------------------------------------------------------------
    # workers (one per copy stream)
    # ------------------------------------------------------------------
    def _run(self, stream: str) -> None:  # repro-role: copy-stream
        q = self._queues[stream]
        while True:
            job = q.get()
            if job is None:
                return
            if self._closed:
                # Teardown: close() only sets _closed after draining every
                # legitimately-launched handle, so a job seen here was
                # enqueued against a closed engine — skip the copy (its
                # pages may already be retired) but still complete the
                # handle so no joiner blocks forever.
                h = job.handle
                if h.error is None:
                    h.error = RuntimeError(
                        "transfer job enqueued after close()")
                with self._lock:
                    h._jobs_done += 1
                    last = h._jobs_done >= h._jobs_total
                if last:
                    h._event.set()
                continue
            h = job.handle
            t0 = time.perf_counter()
            failed = False
            try:
                job.fn()
            except BaseException as e:  # surfaced at join
                h.error = e
                failed = True
            t1 = time.perf_counter()
            with self._lock:
                self.stats.jobs += 1
                self.stats.busy_time += t1 - t0
                self.stats.busy_by_stream[stream] = (
                    self.stats.busy_by_stream.get(stream, 0.0) + (t1 - t0))
                if not failed:
                    self.stats.bytes_by_stream[stream] = (
                        self.stats.bytes_by_stream.get(stream, 0) + job.nbytes)
                # the handle's copy window brackets every shard job of the
                # swap; per-job spans back hidden_bytes (engine) and the
                # traced copy spans (reconcile) — same granularity
                h.copy_start = t0 if h._jobs_done == 0 else min(h.copy_start, t0)
                h.copy_end = max(h.copy_end, t1)
                h._job_spans.append((job.nbytes, t0, t1))
                h._jobs_done += 1
                last = h._jobs_done >= h._jobs_total
            tr = self.tracer
            if tr is not None:
                # emitted BEFORE the event fires so the span exists by the
                # time any join on this handle returns
                tr.emit(f"copy-{stream}", h.kind, t0, t1,
                        {"nbytes": job.nbytes, "iter": h.trace_iter})
            if last:
                h._event.set()

    # ------------------------------------------------------------------
    # launch (engine thread)
    # ------------------------------------------------------------------
    def swap_out(self, req: Request) -> TransferHandle:
        """Device -> host.  Pages/location move now; data moves in background."""
        self._ensure_open()
        dev, host = self.pool.device, self.pool.host
        if not req.pages:
            req.location = "cpu"
            h = TransferHandle("out", req, 0)
            h._event.set()
            return h
        idx = np.asarray(req.pages, np.int32)
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        # Snapshot the device pages to a host staging buffer NOW (the jax
        # gather against the current immutable pool buffers; materialized
        # here so the worker never queues work on the device — on this
        # backend device ops from a second thread would serialize behind the
        # decode graphs and stall the join).  The host-pool scatter — the
        # DRAM-side half of the PCIe move — runs on the worker.
        k_np = np.asarray(dev.k[:, idx])
        v_np = np.asarray(dev.v[:, idx])
        if tr is not None:
            tr.emit("swap", "swap.stage", t0, time.perf_counter(),
                    {"iter": self.trace_iter, "rid": req.rid,
                     "pages": len(req.pages),
                     "bytes": k_np.nbytes + v_np.nbytes})
        new_pages = host.alloc(len(req.pages))
        dev.free(req.pages)
        req.pages = new_pages
        req.location = "cpu"
        L = host.num_layers
        nbytes = k_np.nbytes + v_np.nbytes
        handle = TransferHandle("out", req, nbytes)
        handle.trace_iter = self.trace_iter
        dst_idx = np.asarray(new_pages, np.int32)

        if self.shards == 1:
            def copy() -> None:  # repro-role: copy-stream
                for layer in range(L):  # layer-wise, page-granular scatter
                    host.k[layer, dst_idx] = k_np[layer]
                    host.v[layer, dst_idx] = v_np[layer]
                with self._lock:
                    self.stats.bytes_out += nbytes
                self.pool.add_swap_bytes(nbytes)

            self._queues[self._stream("out")].put(_Job(handle, copy, nbytes))
        else:
            # one job per shard, each scattering its kv-head slice on its
            # own stream; the slices partition the arrays so the per-shard
            # bytes sum EXACTLY to the single-shard total
            KV = k_np.shape[3]
            per = KV // self.shards
            handle._jobs_total = self.shards
            for s in range(self.shards):
                lo, hi = s * per, (s + 1) * per
                nb_s = (k_np[:, :, :, lo:hi].nbytes
                        + v_np[:, :, :, lo:hi].nbytes)

                def copy_shard(lo=lo, hi=hi, nb_s=nb_s) -> None:  # repro-role: copy-stream
                    for layer in range(L):
                        host.k[layer, dst_idx, :, lo:hi] = \
                            k_np[layer, :, :, lo:hi]
                        host.v[layer, dst_idx, :, lo:hi] = \
                            v_np[layer, :, :, lo:hi]
                    with self._lock:
                        self.stats.bytes_out += nb_s
                    self.pool.add_swap_bytes(nb_s)

                self._queues[self._stream("out", s)].put(
                    _Job(handle, copy_shard, nb_s))
        with self._lock:
            self._pending.append(handle)
        return handle

    def swap_in(self, req: Request) -> TransferHandle:
        """Host -> device.  The host pages are gathered into a staging copy
        on the worker (they may not be freed back to the pool until that
        read completes); the device upload + pool scatter happen at join
        time on the engine thread — device ops issued from a second thread
        would contend with the in-flight decode graphs on this backend."""
        self._ensure_open()
        dev, host = self.pool.device, self.pool.host
        if not req.pages:
            req.location = "gpu"
            h = TransferHandle("in", req, 0)
            h._event.set()
            return h
        src_idx = np.asarray(req.pages, np.int32)
        old_pages = req.pages
        new_pages = dev.alloc(len(req.pages))
        req.pages = new_pages
        req.location = "gpu"
        nbytes = 2 * host.k[:, src_idx[:1]].nbytes * len(old_pages)
        handle = TransferHandle("in", req, nbytes)
        handle.trace_iter = self.trace_iter
        staged = {}

        def apply() -> None:  # repro-role: engine -- runs at join time
            host.free(old_pages)
            dev.put_pages(new_pages, staged["k"], staged["v"])

        handle._apply = apply
        if self.shards == 1:
            def gather() -> None:  # repro-role: copy-stream
                # DRAM-side read of the host pages (layer-major contiguous
                # copy); pages return to the host free list only once read.
                staged["k"] = host.k[:, src_idx].copy()
                staged["v"] = host.v[:, src_idx].copy()
                with self._lock:
                    self.stats.bytes_in += nbytes
                self.pool.add_swap_bytes(nbytes)

            self._queues[self._stream("in")].put(_Job(handle, gather, nbytes))
        else:
            # preallocate the full staging buffers NOW; each shard job fills
            # its kv-head slice on its own stream and the staged device
            # write (apply, at join) uploads the assembled whole — the
            # handle's event only fires once every shard landed
            kshape = (host.k.shape[0], len(src_idx)) + host.k.shape[2:]
            staged["k"] = np.empty(kshape, host.k.dtype)
            staged["v"] = np.empty(kshape, host.v.dtype)
            KV = host.k.shape[3]
            per = KV // self.shards
            nb_s = nbytes // self.shards  # exact: slices partition the pages
            handle._jobs_total = self.shards
            for s in range(self.shards):
                lo, hi = s * per, (s + 1) * per

                def gather_shard(lo=lo, hi=hi) -> None:  # repro-role: copy-stream
                    staged["k"][:, :, :, lo:hi] = host.k[:, src_idx, :, lo:hi]
                    staged["v"][:, :, :, lo:hi] = host.v[:, src_idx, :, lo:hi]
                    with self._lock:
                        self.stats.bytes_in += nb_s
                    self.pool.add_swap_bytes(nb_s)

                self._queues[self._stream("in", s)].put(
                    _Job(handle, gather_shard, nb_s))
        with self._lock:
            self._pending.append(handle)
        return handle

    # ------------------------------------------------------------------
    # page-granular copies (prefix cache: promote / demote / COW)
    # ------------------------------------------------------------------
    def copy_pages(self, pages: List[int], src: str, dst: str) -> List[int]:
        """Copy ``pages`` from the ``src`` pool into freshly allocated pages
        of the ``dst`` pool ("gpu" | "cpu"); returns the new page ids.

        Runs synchronously on the caller's thread (device-pool writes must
        stay on the engine thread) with the same PCIe byte accounting as the
        async swap paths.  The source pages are left untouched — the prefix
        cache releases them via refcounted ``free`` when appropriate.
        """
        self._ensure_open()
        src_pool = self.pool.pool(src)
        dst_pool = self.pool.pool(dst)
        if not pages:
            return []
        tr = self.tracer
        t0c = time.perf_counter() if tr is not None else 0.0
        nbytes = 0
        k_np, v_np = src_pool.read_pages(pages)
        new_pages = dst_pool.alloc(len(pages))
        dst_pool.put_pages(new_pages, k_np, v_np)
        if src != dst:  # PCIe crossing: account at the host pool's byte width
            host = self.pool.host
            per_page = 2 * host.k[:, :1].nbytes
            nbytes = per_page * len(pages)
            with self._lock:
                if dst == "cpu":
                    self.stats.bytes_out += nbytes
                else:
                    self.stats.bytes_in += nbytes
            self.pool.add_swap_bytes(nbytes)
        if tr is not None:
            tr.emit("copy-sync", f"{src}->{dst}", t0c, time.perf_counter(),
                    {"pages": len(pages), "nbytes": nbytes})
        return new_pages

    # ------------------------------------------------------------------
    # join
    # ------------------------------------------------------------------
    def join(self, handles: Iterable[TransferHandle], *,
             track: str = "swap") -> None:
        """Block until the given transfers are complete and safe to use.

        Swap-in handles apply their staged device write here — only call
        join() on swap-ins from the engine thread.  Time spent blocked is
        accounted in ``stats.wait_time``; traced, the same interval is a
        ``swap.join`` span on ``track`` (the calling thread's own track),
        with each staged write inside it a ``swap.apply`` span.
        """
        tr = self.tracer
        t0 = time.perf_counter()
        try:
            for h in handles:
                h._event.wait()
                with self._lock:
                    h._joined = True  # consumed even on error — a failed
                    # handle must not poison later drain()/close() calls
                    apply, h._apply = h._apply, None
                if h.error is not None:
                    raise h.error
                if apply is not None:
                    a0 = time.perf_counter() if tr is not None else 0.0
                    apply()
                    if tr is not None:
                        tr.emit(track, "swap.apply", a0, time.perf_counter(),
                                {"iter": h.trace_iter, "rid": h.req.rid,
                                 "bytes": h.nbytes})
        finally:
            t1 = time.perf_counter()
            with self._lock:
                self.stats.wait_time += t1 - t0
                self._pending = [p for p in self._pending if not p._joined]
            if tr is not None:
                tr.emit(track, "swap.join", t0, t1, {"iter": self.trace_iter})

    def join_requests(self, reqs: Iterable[Request],
                      kind: Optional[str] = None, *,
                      track: str = "swap") -> None:
        """Lane-scoped join point: block until every pending transfer whose
        request is in ``reqs`` (optionally restricted to ``kind`` "out" /
        "in") is complete.

        This is what each host-lane dispatch thread calls right before its
        host attention reads the lane's pages — swap-outs join against the
        lane that consumes them rather than one global barrier.  Only call
        with ``kind="in"`` (or ``kind=None`` over swap-ins) from the engine
        thread: swap-in joins apply a staged device write.  ``track`` names
        the calling thread's trace track (see :meth:`join`).
        """
        rids = {r.rid for r in reqs}
        with self._lock:
            hs = [h for h in self._pending
                  if h.req.rid in rids and (kind is None or h.kind == kind)]
        self.join(hs, track=track)

    def drain(self) -> None:
        """Join every outstanding transfer (step barrier / shutdown)."""
        self.join(list(self._pending))

    def close(self, timeout: float = 5.0) -> None:
        """Idempotent shutdown: drain every outstanding transfer, stop the
        worker threads via queue sentinels, and join them with a timeout.

        A transfer that failed in flight must not leave the workers
        running: its error is captured, the remaining handles keep
        draining, and the first error re-raises only after every worker
        has been joined.  After close() returns, swap_out/swap_in/
        copy_pages raise rather than enqueue onto dead queues.
        """
        if self._closed:
            return
        errors: List[BaseException] = []
        # Drain to quiescence.  join() marks a failed handle consumed
        # before raising, so each failed round strictly shrinks
        # self._pending and this loop terminates.
        while True:
            try:
                self.drain()
                break
            except BaseException as e:
                errors.append(e)
        self._closed = True
        for q in self._queues.values():
            q.put(None)
        for s, w in self._workers.items():
            w.join(timeout=timeout)
            if w.is_alive():
                errors.append(RuntimeError(
                    f"copy-stream worker {s!r} did not exit within "
                    f"{timeout:.1f}s of its shutdown sentinel"))
        if errors:
            raise errors[0]

    def _ensure_open(self) -> None:
        if self._closed:
            raise RuntimeError("TransferEngine is closed")
