"""NeoEngine — the online serving engine (continuous batching + NEO offload).

One :meth:`step` = one inference iteration (Fig. 5), executed in explicit
**plan → launch → join** phases:

* **plan** — the load-aware scheduler builds the two-batch asymmetric plan.
* **launch** — KV swaps start on the :class:`TransferEngine`'s background
  worker (page-granular, layer-wise); queue moves commit; the prefill
  sub-batch dispatches while those copies are in flight.
* **join** — the plan executes as a **unified lane plan**: one optional
  device lane (prefill + batch-0's fused graph, engine thread) plus K >= 0
  host lanes (fused host-only graphs on the executor's lane threads), where
  the scheduler's ``lane_splits`` partition batch-1.  Swap-outs join
  lane-scoped on the lane that decodes them, right before its host
  attention reads the pages; swap-ins join on the engine thread right
  before the device graph consumes the pool.  All lanes' logits join and
  new tokens are sampled in plan order, so greedy decode is bitwise
  identical to the serial path (``pipeline=False``).  K=1 under a
  prefill-long device lane is the classic asymmetric two-batch overlap;
  batch-1-ONLY plans (no device lane — the FastDecode+/full-offload regime)
  split into K >= 2 alternating lanes so one lane's host attention overlaps
  the others' linear stages; and mixed decode-only plans with a SHORT
  device lane **borrow** those lanes for their surplus host rows instead of
  serializing them behind the short device dispatch.

**Speculative decoding** (``EngineConfig.spec_decode``; SpecOffload-style)
rides the step after the base decode emits: drafting rows expand into
pseudo-rows at successive KV positions and ONE extra batched pass of the
UNCHANGED fused decode graph verifies every draft position at once
(:meth:`_run_spec_chain`) — the pass recomputes the exact logits serial
decode would produce at each position, so greedy outputs stay bitwise
identical to non-speculative decode BY CONSTRUCTION; a rejection leaves
``out_tokens`` at the serially-correct emission (drafts are fed through
detached pseudo-rows, never the row itself) and rolls back the pages the
chain grew (never a page the row held before the chain, so prefix-shared
pages are structurally untouchable).  Verify wall time accrues to
``EngineStats.spec_busy_time`` (NOT device/lane busy time) and its spans
ride the dedicated unaudited ``spec`` track, keeping
:func:`repro.obs.reconcile.reconcile` green by construction.

:class:`EngineStats` records the *measured* overlap (pipeline bubble
fraction, swap bytes hidden under compute, host-vs-device busy time), which
also feeds :meth:`PerfModel.observe_iteration` so calibration sees real
rather than modelled stage times.

Fault tolerance: every accepted request is journaled (prompt + sampling params
+ emitted tokens).  :meth:`export_journal` / :meth:`replay_journal` implement
prefill-replay recovery — after an engine loss, unfinished requests resume by
prefilling ``prompt + tokens_so_far`` (decode continues exactly where it
stopped; emitted tokens are never re-issued).
"""

from __future__ import annotations

import copy
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.config import ArchConfig, EngineConfig
from repro.core.executor import ContiguousExecutor, PagedExecutor
from repro.core.host_attention import HostAttention
from repro.core.kv_cache import DualPool
from repro.core.perfmodel import PerfModel
from repro.core.prefix_cache import PrefixCache
from repro.core.request import Request, RequestState
from repro.core.scheduler import BatchPlan, NeoScheduler, PoolView, SchedQueues
from repro.core.transfer import TransferEngine
from repro.models.api import get_model
from repro.obs.tracer import SpanTracer

PAGED_FAMILIES = ("dense", "moe", "vlm")


@dataclass
class EngineStats:
    iterations: int = 0
    tokens_out: int = 0
    prefill_tokens: int = 0
    # slots of the prefill graphs that computed them (batch x bucket, pads
    # included): 1 - prefill_tokens / prefill_slot_tokens is the pad share
    prefill_slot_tokens: int = 0
    mode_counts: Dict[str, int] = field(default_factory=dict)
    offloaded_decodes: int = 0
    device_decodes: int = 0
    wall_time: float = 0.0
    host_busy_time: float = 0.0
    # -- measured pipeline overlap (Fig. 5, realized) ----------------------
    # device_busy_time: wall time of prefill + batch-0 dispatches (the lane
    # batch-1 is supposed to hide under).
    device_busy_time: float = 0.0
    # pipeline_overlap_time: measured intersection of the two lanes'
    # dispatch windows (batch-0 vs batch-1, or micro-batch A vs B);
    # pipeline_ideal_time: the shorter lane's duration (perfect pipelining
    # would hide all of it).  Serialized batch-1-only steps contribute
    # ideal-but-no-overlap time (the hideable half of the lane ran
    # unhidden), so bubble_fraction stays honest when one lane is empty.
    pipeline_overlap_time: float = 0.0
    pipeline_ideal_time: float = 0.0
    pipelined_steps: int = 0
    # -- unified lane plans (K host lanes + optional device lane) ----------
    microbatched_steps: int = 0  # batch-1-only steps split into >= 2 lanes
    serial_b1_steps: int = 0  # batch-1-only steps that ran inline (no split)
    # mixed plans (short decode-only device lane) that BORROWED >= 2 host
    # lanes for their surplus batch-1 rows instead of serializing them
    borrowed_lane_steps: int = 0
    # histogram: number of host lanes K -> steps executed with that K
    lane_counts: Dict[int, int] = field(default_factory=dict)
    # per-lane dispatch wall time: "prefill" / "batch0" (device lane),
    # "host0".."hostK-1" (host lanes; "host0" is the classic batch-1 lane)
    # and "serial" (the pipeline=False fused path)
    lane_busy_time: Dict[str, float] = field(default_factory=dict)
    # -- transfer engine mirror (async swaps) ------------------------------
    swap_out_bytes: int = 0
    swap_in_bytes: int = 0
    swap_hidden_bytes: int = 0  # copies that finished before anyone joined
    swap_wait_time: float = 0.0  # time the compute lanes blocked on joins
    # -- plan-ahead scheduling ---------------------------------------------
    # hits: iterations that reused the speculative plan built while the
    # previous iteration's lanes executed (plan phase off the critical path);
    # replans: speculation falsified (arrival/departure/preemption/eos) and
    # the iteration planned fresh; skipped: iterations whose post-step state
    # was not predictable enough to speculate on (cache mutations etc.)
    planahead_hits: int = 0
    planahead_replans: int = 0
    planahead_skipped: int = 0
    # critical-path planning wall time (fresh plans + harvest waits) vs the
    # planner-thread time hidden under lane execution by accepted plans
    plan_busy_time: float = 0.0
    planahead_hidden_time: float = 0.0
    # open-loop admission control: arrivals bounced by offer()
    rejected_requests: int = 0
    # -- speculative decoding ----------------------------------------------
    # steps that ran a verify chain; drafted/accepted/rejected token counts
    # (rejected_drafts == drafted_tokens - accepted_tokens); chain wall time
    # (kept OUT of device/lane busy time so reconcile()'s audit is
    # untouched); histogram: accepted chain length -> drafting-row count
    spec_steps: int = 0
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    rejected_drafts: int = 0
    spec_busy_time: float = 0.0
    accept_len_hist: Dict[int, int] = field(default_factory=dict)
    plans: List[str] = field(default_factory=list)

    def record_plan(self, plan: BatchPlan) -> None:
        self.mode_counts[plan.mode] = self.mode_counts.get(plan.mode, 0) + 1
        if len(self.plans) < 1000:
            self.plans.append(plan.summary())

    def lane_add(self, lane: str, dt: float) -> None:
        self.lane_busy_time[lane] = self.lane_busy_time.get(lane, 0.0) + dt

    @property
    def bubble_fraction(self) -> float:
        """1 - realized/ideal overlap (0 = no bubble).  NaN-free and
        lane-aware: ideal time accumulates the shorter lane of every
        two-lane step AND the hideable half of serialized batch-1-only
        steps (where overlap was structurally possible but zero was
        realized), so a fully serialized host-attention workload reports a
        bubble near 1.0 rather than a misleading 0.0.  With no hideable
        work at all there is nothing to pipeline: 0.0."""
        if self.pipeline_ideal_time <= 0:
            return 0.0
        return min(1.0, max(
            0.0, 1.0 - self.pipeline_overlap_time / self.pipeline_ideal_time))

    @property
    def host_device_busy_ratio(self) -> float:
        """Host-attention busy time over device-lane busy time, NaN-free:
        a host-only workload (empty device lane, e.g. batch-1-only plans)
        reports +inf rather than a misleading 0.0; fully idle reports 0.0."""
        if self.device_busy_time <= 0:
            return float("inf") if self.host_busy_time > 0 else 0.0
        return self.host_busy_time / self.device_busy_time


class _SpecRow:
    """Lightweight decode-row view for batched draft verification: feeds one
    token at an advanced KV position over the REAL row's page table (shared
    list — the verify pass scatters its KV into the same pooled pages).
    Carries exactly the fields :meth:`PagedExecutor.decode` reads."""

    __slots__ = ("all_tokens", "kv_len", "pages")

    def __init__(self, token: int, kv_len: int, pages: List[int]):
        self.all_tokens = (token,)
        self.kv_len = kv_len
        self.pages = pages


class NeoEngine:
    def __init__(
        self,
        cfg: ArchConfig,
        engine_cfg: EngineConfig = EngineConfig(),
        *,
        params: Optional[Dict[str, Any]] = None,
        rng: Optional[jax.Array] = None,
        kernel_impl: Optional[str] = None,
    ):
        """``kernel_impl`` overrides the platform's choice of device decode
        attention ("ref", "pallas" or "interpret"); tests only."""
        self.cfg = cfg
        self.engine_cfg = engine_cfg
        self.model = get_model(cfg)
        if params is None:
            params = self.model.init(rng if rng is not None else jax.random.key(engine_cfg.seed))
        self.params = params
        tp = max(1, int(engine_cfg.tp))
        self.tp = tp
        mesh = None
        if tp > 1:
            devs = jax.devices()
            if tp > len(devs):
                raise ValueError(
                    f"tp={tp} exceeds the {len(devs)} available device(s); "
                    "start with XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "or lower --tp")
            # The engine builds its own (1, tp) mesh over the first tp
            # devices: a data-replicated mesh would instantiate duplicate
            # shard_map bodies whose host callbacks race on shared state.
            mesh = Mesh(np.asarray(devs[:tp]).reshape(1, tp), ("data", "model"))
        self.mesh = mesh
        self.perf = PerfModel.for_arch(cfg, engine_cfg.hw_profile,
                                       engine_cfg.ewma_alpha, tp=tp)
        self.scheduler = NeoScheduler(cfg, engine_cfg, self.perf)
        self.paged = cfg.family in PAGED_FAMILIES and cfg.supports_offload
        if tp > 1 and not self.paged:
            raise ValueError("tp > 1 requires the paged engine "
                             "(dense family with offload support)")
        if self.paged:
            self.pool = DualPool(cfg, engine_cfg.device_pool_pages,
                                 engine_cfg.host_pool_pages, mesh=mesh)
            self._scratch = self.pool.device.alloc(1)  # page 0 = decode scratch
            self.host_attn = HostAttention(
                cfg, self.pool.host.k, self.pool.host.v, threads=engine_cfg.host_threads
            )
            self.executor = PagedExecutor(
                self.model, params, self.pool, self.host_attn,
                impl=kernel_impl, host_lanes=engine_cfg.max_host_lanes,
                tp=tp, mesh=mesh,
            )
            self.transfer = TransferEngine(self.pool, shards=tp)
            self._page = cfg.kv_block_size
            # Two-tier radix prefix cache (off by default: the uncached path
            # stays bitwise identical to the pre-cache engine).
            self.prefix_cache = (
                PrefixCache(self.pool, self.transfer,
                            token_granular=engine_cfg.prefix_token_granular)
                if engine_cfg.prefix_cache else None
            )
            # Speculative-decoding drafter (injectable: serve.py swaps in a
            # DraftModelDrafter for --draft-model, tests inject stubs).  The
            # drafter is a pure token-level oracle — all KV/page bookkeeping
            # stays in _run_spec_chain.
            self.drafter = None
            if engine_cfg.spec_decode:
                from repro.core.spec import NgramDrafter
                self.drafter = NgramDrafter(engine_cfg.spec_ngram)
        else:
            slots = min(engine_cfg.max_requests, 64)
            capacity = engine_cfg.max_batch_tokens
            self.executor = ContiguousExecutor(
                self.model, params, slots=slots, capacity=capacity
            )
            self._page = capacity  # 1 "page" == 1 slot in scheduler accounting
            self.pool = None
            self.host_attn = None
            self.transfer = None
            self.prefix_cache = None
            self.drafter = None  # speculation is a paged-engine feature
        self._rng = np.random.default_rng(engine_cfg.seed)
        self._next_rid = 0
        self.requests: Dict[int, Request] = {}
        self.stats = EngineStats()
        # Structured tracing (repro.obs): off by default.  Every call site
        # guards on ``tracer is not None`` so the traced and untraced paths
        # run the same computation — greedy outputs are bitwise identical.
        self.tracer: Optional[SpanTracer] = None
        if engine_cfg.tracing:
            self.attach_tracer(SpanTracer(engine_cfg.trace_buffer))
        self._journal: List[Dict[str, Any]] = []
        self.clock = 0.0  # virtual clock (arrival bookkeeping in offline runs)
        # plan-ahead: a single planner thread (lazily started) builds the
        # NEXT iteration's plan against a shadow of the post-step state while
        # this iteration's lanes execute; _spec holds the in-flight
        # speculation as (predicted_signature, shadow_state, shadows, future)
        self._planner: Optional[ThreadPoolExecutor] = None
        self._spec: Optional[Tuple[Any, SchedQueues, Dict[int, Request], Any]] = None

    def attach_tracer(self, tracer: Optional[SpanTracer]) -> None:
        """(Re)wire ``tracer`` through every instrumented component — also
        used by benchmarks that reset stats after a warmup phase and need a
        fresh span timeline that stays reconcilable against them."""
        self.tracer = tracer
        self.scheduler.tracer = tracer
        if self.paged:
            self.executor.tracer = tracer
            self.transfer.tracer = tracer
            if self.prefix_cache is not None:
                self.prefix_cache.tracer = tracer

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        *,
        arrival_time: Optional[float] = None,
        eos_token: Optional[int] = None,
        extras: Optional[Dict[str, np.ndarray]] = None,
    ) -> int:
        rid = self._next_rid
        self._next_rid += 1
        req = Request(
            rid=rid,
            prompt=list(map(int, prompt)),
            max_new_tokens=int(max_new_tokens),
            arrival_time=self.clock if arrival_time is None else arrival_time,
            eos_token=eos_token,
        )
        if extras:
            req.extras = extras  # type: ignore[attr-defined]
        if self.prefix_cache is not None and not extras:
            # longest-prefix match (estimate only; re-validated and pinned at
            # prefill dispatch) so the scheduler prices the prefill correctly
            # — residency steers host placement (zero-copy host serving)
            # (multimodal prompts are not prefix-cached)
            req.cached_len, req.prefix_loc = self.prefix_cache.lookup_ex(req.prompt)
        self.requests[rid] = req
        self.scheduler.add_request(req)
        self._journal.append(
            {
                "rid": rid,
                "prompt": list(req.prompt),
                "max_new_tokens": req.max_new_tokens,
                "arrival_time": req.arrival_time,
                "eos_token": eos_token,
                "out_tokens": req.out_tokens,  # aliased: auto-updates
            }
        )
        if self.tracer is not None:
            self.tracer.async_begin(rid, "req", args={
                "prompt_len": len(req.prompt),
                "max_new_tokens": req.max_new_tokens})
        return rid

    def offer(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        *,
        arrival_time: Optional[float] = None,
        eos_token: Optional[int] = None,
        extras: Optional[Dict[str, np.ndarray]] = None,
    ) -> Optional[int]:
        """Admission-controlled :meth:`submit` for the open-loop front end:
        returns ``None`` (and counts the rejection) when the waitqueue is at
        the configured ``max_waiting`` depth.  ``submit`` keeps the
        closed-loop everything-is-admitted behavior."""
        if not self.scheduler.has_capacity():
            self.stats.rejected_requests += 1
            if self.tracer is not None:
                self.tracer.instant("engine", "reject",
                                    {"reason": "max_waiting"})
            return None
        return self.submit(prompt, max_new_tokens, arrival_time=arrival_time,
                           eos_token=eos_token, extras=extras)

    def cancel(self, rid: int) -> bool:
        """Mid-flight departure (client disconnect / streaming abort): free
        the request's KV, drop it from the scheduler queues, and mark it
        ABORTED.  Tokens already streamed stay with the caller.  Call
        between steps (the engine API is single-threaded; transfers drain at
        the end of every step, so no in-flight copy references the pages)."""
        req = self.requests.get(rid)
        if req is None or req.state in (RequestState.FINISHED, RequestState.ABORTED):
            return False
        if req.pages:
            if self.paged:
                pool = self.pool.device if req.location == "gpu" else self.pool.host
                pool.free(req.pages)  # refcounted: shared prefix pages survive
            else:
                self.executor.free_slot(req.pages[0])
            req.pages = []
        sched = self.scheduler
        if req in sched.waitq:
            sched.waitq.remove(req)
        if req in sched.gpu_runq:
            sched.gpu_runq.remove(req)
        if req in sched.cpu_runq:
            sched.cpu_runq.remove(req)
        req.state = RequestState.ABORTED
        req.finish_time = self.clock
        if self.tracer is not None:
            self.tracer.async_end(rid, "req", args={
                "outcome": "cancelled", "tokens": len(req.out_tokens)})
        return True

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _pool_view(self) -> PoolView:
        if self.paged:
            dev_evict = host_evict = 0
            if self.prefix_cache is not None:
                # unpinned cached pages are reclaimable on demand (make_room),
                # so the scheduler plans against free + evictable
                dev_evict = self.prefix_cache.evictable_pages("gpu")
                host_evict = self.prefix_cache.evictable_pages("cpu")
            return PoolView(
                page_size=self._page,
                device_free=self.pool.device.free_pages + dev_evict,
                host_free=self.pool.host.free_pages + host_evict,
                device_total=self.pool.device.num_pages - 1,  # minus scratch
                host_total=self.pool.host.num_pages,
            )
        return PoolView(
            page_size=self._page,
            device_free=len(self.executor.free_slots),
            host_free=0,
            device_total=self.executor.slots,
            host_total=0,
        )

    def _sample(self, logits: np.ndarray) -> int:
        if self.engine_cfg.decode_sample == "greedy":
            return int(np.argmax(logits))
        z = logits.astype(np.float64)
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    def _emit(self, req: Request, logits: np.ndarray, now: float,
              emitted: List[Tuple[int, int]]) -> None:
        tok = self._sample(logits)
        req.out_tokens.append(tok)
        if req.first_token_time is None:
            req.first_token_time = now
        emitted.append((req.rid, tok))
        self.stats.tokens_out += 1

    def _finish(self, req: Request, now: float) -> None:
        req.state = RequestState.FINISHED
        req.finish_time = now
        if self.tracer is not None:
            self.tracer.async_end(req.rid, "req", args={
                "outcome": "finished", "tokens": len(req.out_tokens)})
        if self.paged:
            if req.pages:
                pool = self.pool.device if req.location == "gpu" else self.pool.host
                if self.prefix_cache is not None:
                    # adopt the full pages into the radix tree (tree takes its
                    # own reference), THEN release the request's references —
                    # adopted and still-shared pages survive, the rest free
                    self.prefix_cache.insert_request(req)
                pool.free(req.pages)
        else:
            if req.pages:
                self.executor.free_slot(req.pages[0])
        req.pages = []

    @staticmethod
    def _extras_batch(reqs: List[Request], S: int) -> Dict[str, jnp.ndarray]:
        ex = [getattr(r, "extras", None) for r in reqs]
        if not any(ex):
            return {}
        keys = set().union(*[set(e) for e in ex if e])
        out = {}
        for k in keys:
            rows = [e[k] if e and k in e else np.zeros_like(next(iter(
                e2[k] for e2 in ex if e2 and k in e2))) for e in ex]
            out[k] = jnp.asarray(np.stack(rows))
        return out

    # ------------------------------------------------------------------
    # plan-ahead scheduling
    # ------------------------------------------------------------------
    # While iteration N's lanes execute, a planner thread builds iteration
    # N+1's plan against a SHADOW of the predicted post-step queues and pool
    # counters (the view-based scheduler makes planning side-effect-free for
    # the live queues).  At step N+1 the speculation is validated by
    # comparing a signature over every plan input — per-request scheduling
    # fields plus the free-page view — against the real state: a match
    # adopts the plan (remapped shadow→real) with zero planning on the
    # critical path; a mismatch (arrival, cancel, eos finish, anything the
    # simulation could not see) replans fresh.  Prediction accuracy only
    # affects the hit rate, never correctness — and greedy outputs are
    # bitwise identical under ANY plan shape (row-independent per-row
    # compute), so even a speculation built from stale EWMA scales is safe.

    @staticmethod
    def _sig_req(r: Request) -> tuple:
        # every per-request field the six-step procedure reads (kv_len /
        # prefill_len / suffix_len / pages_needed derive from these)
        return (r.rid, r.state.value, r.location, len(r.prompt),
                len(r.out_tokens), len(r.pages), r.skipped, r.cached_len,
                r.prefix_loc, r.max_new_tokens)

    @staticmethod
    def _sig_of(waitq, gpu_runq, cpu_runq, dev_free: int, host_free: int) -> tuple:
        f = NeoEngine._sig_req
        return (tuple(f(r) for r in waitq), tuple(f(r) for r in gpu_runq),
                tuple(f(r) for r in cpu_runq), dev_free, host_free)

    def _signature(self) -> tuple:
        pv = self._pool_view()
        s = self.scheduler
        return self._sig_of(s.waitq, s.gpu_runq, s.cpu_runq,
                            pv.device_free, pv.host_free)

    def _build_shadow(self, plan: BatchPlan):
        """Predict the post-step scheduler/pool state for ``plan`` (called
        right after commit, before dispatch) and clone it into shadows the
        planner thread can mutate freely.

        Returns ``(state, shadows, pools_pred, sig_pred)`` or ``None`` when
        the remainder of the step is not predictable by page arithmetic
        alone — with the prefix cache on, anything that touches the radix
        tree (prefill pins, preemption/swap frees of possibly-shared pages,
        finish-time inserts, growth under eviction pressure) is skipped
        rather than simulated.
        """
        page = self._page
        cache_on = self.prefix_cache is not None
        sched = self.scheduler
        if cache_on and (plan.prefill or plan.preempt
                         or plan.swap_out or plan.swap_in):
            return None

        # pool counters as they will stand at the end of the step: swaps'
        # page accounting already moved at launch, except swap-in source
        # pages which return to the host pool at join-apply (drained by the
        # step barrier)
        dev_raw = self.pool.device.free_pages
        host_raw = self.pool.host.free_pages + sum(
            len(r.pages) for r in plan.swap_in)

        def _running(rs: List[Request]) -> List[Request]:
            return [r for r in rs
                    if r.state == RequestState.RUNNING and r not in plan.prefill]

        rows = (_running(plan.decode_gpu) + _running(plan.decode_cpu0)
                + _running(plan.decode_cpu1))

        shadows: Dict[int, Request] = {}

        def clone(r: Request) -> Request:
            sr = copy.copy(r)
            sr.out_tokens = list(r.out_tokens)
            sr.pages = list(r.pages)
            shadows[r.rid] = sr
            return sr

        st = SchedQueues(
            waitq=deque(clone(r) for r in sched.waitq),
            gpu_runq=[clone(r) for r in sched.gpu_runq],
            cpu_runq=[clone(r) for r in sched.cpu_runq],
        )

        # decode-row page growth (same predicate as dispatch, evaluated on
        # the pre-emission kv_len) + token emission.  -1 is a placeholder:
        # signatures only read lengths, and it can never equal an eos token,
        # so a real eos finish falsifies the signature instead of silently
        # matching.
        for r in rows:
            sr = shadows[r.rid]
            host = r.location == "cpu"
            if r.kv_len % page == 0 and r.kv_len // page >= len(r.pages):
                if cache_on and (host_raw if host else dev_raw) < 1:
                    return None  # make_room would evict: not predictable
                if host:
                    host_raw -= 1
                else:
                    dev_raw -= 1
                sr.pages.append(-1)
            sr.out_tokens.append(-1)

        # prefill allocation + first-token emission (cache off here; the
        # cache-on prefill path was excluded above).  Replayed prefills
        # (recompute preemption) re-derive their last token and do not emit.
        for r in plan.prefill:
            sr = shadows[r.rid]
            npages = -(-r.prefill_len // page)
            if r in plan.prefill_to_host:
                host_raw -= npages
            else:
                dev_raw -= npages
            sr.pages = [-1] * npages
            if not sr.out_tokens:
                sr.out_tokens.append(-1)

        # finishes: only the max_new_tokens bound is predictable (an eos
        # emission falsifies the signature and replans)
        for r in plan.prefill + plan.decode_rows:
            sr = shadows.get(r.rid)
            if sr is None or sr.state != RequestState.RUNNING:
                continue
            if len(sr.out_tokens) >= sr.max_new_tokens:
                if cache_on:
                    return None  # finish inserts into the radix tree
                if sr.location == "cpu":
                    host_raw += len(sr.pages)
                else:
                    dev_raw += len(sr.pages)
                sr.state = RequestState.FINISHED
                sr.pages = []
        st.gpu_runq = [r for r in st.gpu_runq if r.state != RequestState.FINISHED]
        st.cpu_runq = [r for r in st.cpu_runq if r.state != RequestState.FINISHED]

        if dev_raw < 0 or host_raw < 0:
            return None  # simulation diverged from the scheduler's budget

        dev_ev = host_ev = 0
        if cache_on:
            # pure-decode steps leave the radix tree untouched (everything
            # else returned None above), so evictable counts are stable
            dev_ev = self.prefix_cache.evictable_pages("gpu")
            host_ev = self.prefix_cache.evictable_pages("cpu")
        pools_pred = PoolView(
            page_size=page,
            device_free=dev_raw + dev_ev,
            host_free=host_raw + host_ev,
            device_total=self.pool.device.num_pages - 1,
            host_total=self.pool.host.num_pages,
        )
        sig_pred = self._sig_of(st.waitq, st.gpu_runq, st.cpu_runq,
                                pools_pred.device_free, pools_pred.host_free)
        return st, shadows, pools_pred, sig_pred

    def _launch_planahead(self, plan: BatchPlan) -> None:
        """Kick off the speculative plan for the NEXT iteration (called after
        commit, before dispatch, so the planner overlaps the lane windows).
        The planner thread touches only shadow requests and its own pool
        view — never the live queues the executing lanes read."""
        self._spec = None
        shadow = self._build_shadow(plan)
        if shadow is None:
            self.stats.planahead_skipped += 1
            return
        st, shadows, pools_pred, sig_pred = shadow
        sched = self.scheduler
        tr = self.tracer

        def _plan_spec():
            t0 = time.perf_counter()
            p = sched.plan(pools_pred, state=st)
            dur = time.perf_counter() - t0
            if tr is not None:
                tr.emit("planner", "spec_plan", t0, t0 + dur, {"dur": dur})
            return p, dur

        if self._planner is None:
            self._planner = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="neo-planner")
        self._spec = (sig_pred, st, shadows, self._planner.submit(_plan_spec))

    def _take_plan(self) -> Tuple[Optional[BatchPlan], bool]:
        """Harvest the in-flight speculation: ``(plan, False)`` on a hit,
        ``(None, had_spec)`` otherwise (the caller plans fresh; had_spec
        marks a REPLAN whose fresh planning time was hideable)."""
        spec, self._spec = self._spec, None
        if spec is None:
            return None, False
        sig_pred, st, shadows, fut = spec
        tr = self.tracer
        t0 = time.perf_counter()
        err = False
        try:
            plan_s, dur = fut.result()
        except Exception:
            err = True
        # harvest wait (planner still running = the rare case where planning
        # outlasted the lanes) is genuine critical-path plan time
        wait = time.perf_counter() - t0
        self.stats.plan_busy_time += wait
        if tr is not None:
            tr.emit("engine", "plan_harvest", t0, t0 + wait, {"dur": wait})
        if err:
            self.stats.planahead_replans += 1
            return None, True
        if self._signature() != sig_pred:
            self.stats.planahead_replans += 1
            return None, True
        real = self.requests

        def rmap(rs: List[Request]) -> List[Request]:
            return [real[sr.rid] for sr in rs]

        plan = BatchPlan(
            mode=plan_s.mode,
            prefill=rmap(plan_s.prefill),
            prefill_to_host=rmap(plan_s.prefill_to_host),
            decode_gpu=rmap(plan_s.decode_gpu),
            decode_cpu0=rmap(plan_s.decode_cpu0),
            decode_cpu1=rmap(plan_s.decode_cpu1),
            swap_out=rmap(plan_s.swap_out),
            swap_in=rmap(plan_s.swap_in),
            preempt=rmap(plan_s.preempt),
            lane_splits=list(plan_s.lane_splits),
            spec_k=plan_s.spec_k,
            est_iter_time=plan_s.est_iter_time,
            est_tokens=plan_s.est_tokens,
            stages=plan_s.stages,
        )
        # planning's own queue/request mutations ran on the shadows; apply
        # them to the real state exactly as a fresh plan would have: aging
        # (skipped), admission aborts, and the post-plan waitqueue (pops +
        # step-5 bounces, in shadow order)
        for sr in shadows.values():
            r = real.get(sr.rid)
            if r is None:
                continue
            r.skipped = sr.skipped
            if (sr.state == RequestState.ABORTED
                    and r.state == RequestState.WAITING):
                r.state = RequestState.ABORTED
        self.scheduler.waitq = deque(real[sr.rid] for sr in st.waitq)
        self.stats.planahead_hits += 1
        # the planner's wall time was hidden under iteration N's lanes:
        # realized AND ideal overlap both grow by it, keeping bubble_fraction
        # comparable with the lockstep path (which pays it as a bubble)
        self.stats.planahead_hidden_time += dur
        self.stats.pipeline_overlap_time += dur
        self.stats.pipeline_ideal_time += dur
        if tr is not None:
            tr.instant("engine", "plan_adopt", {"dur": dur})
        return plan, False

    def _host_busy_total(self) -> float:
        """Host-attention busy seconds summed over the engine-level instance
        and the executor's per-shard instances (TP device-lane callbacks)."""
        if not self.host_attn:
            return 0.0
        t = self.host_attn.busy_time
        for s in getattr(self.executor, "host_shards", []) or []:
            t += s.busy_time
        return t

    def _host_prefix_busy_total(self) -> float:
        if not self.host_attn:
            return 0.0
        t = self.host_attn.prefix_busy_time
        for s in getattr(self.executor, "host_shards", []) or []:
            t += s.prefix_busy_time
        return t

    # ------------------------------------------------------------------
    # one iteration
    # ------------------------------------------------------------------
    def step(self, now: Optional[float] = None) -> List[Tuple[int, int]]:
        """Run one inference iteration; returns [(rid, new_token), ...]."""
        t0 = time.perf_counter()
        now = self.clock if now is None else now
        self.clock = now
        host_busy0 = self._host_busy_total()
        prefix_busy0 = self._host_prefix_busy_total()
        dev_busy0 = self.stats.device_busy_time
        spec_busy0 = self.stats.spec_busy_time
        swap_busy0 = self.transfer.stats.busy_time if self.transfer else 0.0

        # -- PLAN --------------------------------------------------------------
        # plan-ahead first: adopt the plan speculated during the previous
        # iteration when its predicted state still matches reality
        plan = None
        replanned = False
        if self.paged:
            plan, replanned = self._take_plan()
        if plan is None:
            p0 = time.perf_counter()
            plan = self.scheduler.plan(self._pool_view())
            dt = time.perf_counter() - p0
            self.stats.plan_busy_time += dt
            if self.tracer is not None:
                self.tracer.emit("engine", "plan_fresh", p0, p0 + dt,
                                 {"dur": dt, "hideable": replanned})
            if replanned:
                # a falsified speculation means this planning time WAS
                # hideable (the planner thread sat idle while the previous
                # lanes ran): account it as unrealized-but-ideal overlap so
                # bubble_fraction reflects the missed win
                self.stats.pipeline_ideal_time += dt
        if plan.is_empty():
            return []
        self.stats.iterations += 1
        self.stats.record_plan(plan)

        # -- LAUNCH / DISPATCH / JOIN (paged) ----------------------------------
        emitted: List[Tuple[int, int]] = []
        if self.paged:
            self._step_paged(plan, now, emitted)
        else:
            self._step_contiguous(plan, now, emitted)

        # -- finish bookkeeping ------------------------------------------------
        for req in plan.prefill + plan.decode_rows:
            if req.state == RequestState.RUNNING and req.is_done():
                self._finish(req, now)
        self.scheduler.remove_finished()

        # -- perf-model refresh from MEASURED stage times (EWMA; straggler
        #    mitigation) — the pipelined path reports real overlap, not the
        #    modelled one ------------------------------------------------------
        t_iter = time.perf_counter() - t0
        self.stats.wall_time += t_iter
        host_busy = 0.0
        if self.host_attn:
            host_busy = self._host_busy_total() - host_busy0
            self.stats.host_busy_time += host_busy
        if self.transfer:
            ts = self.transfer.stats
            self.stats.swap_out_bytes = ts.bytes_out
            self.stats.swap_in_bytes = ts.bytes_in
            self.stats.swap_wait_time = ts.wait_time
        if self.paged:
            # per-shard host-attention instances run concurrently, so their
            # summed busy time approximates tp x the wall time the perf model
            # prices — divide before calibrating (exact no-op at tp=1)
            self.perf.observe_iteration(
                plan.stages,
                host_busy=host_busy / self.tp,
                device_busy=self.stats.device_busy_time - dev_busy0,
                swap_busy=(self.transfer.stats.busy_time - swap_busy0)
                if self.transfer else 0.0,
                host_prefix_busy=(self._host_prefix_busy_total() - prefix_busy0)
                / self.tp if self.host_attn else 0.0,
                spec_busy=self.stats.spec_busy_time - spec_busy0,
                pipelined=self.engine_cfg.pipeline and plan.mode != "serial",
            )
        if self.tracer is not None:
            self.tracer.emit("engine", "step", t0, time.perf_counter(),
                             {"iter": self.stats.iterations})
            self.tracer.counter("queues", self.scheduler.queue_depths())
            if self.paged:
                self.tracer.counter("pool_free", {
                    "device": self.pool.device.free_pages,
                    "host": self.pool.host.free_pages})
        return emitted

    # -- paged families ------------------------------------------------------
    def _step_paged(self, plan: BatchPlan, now: float, emitted: List[Tuple[int, int]]) -> None:
        # "serial"-mode plans (strawman #1) must execute without overlap by
        # definition; everything else pipelines when enabled.
        pipelined = self.engine_cfg.pipeline and plan.mode != "serial"
        tr = self.tracer
        it = self.stats.iterations
        if tr is not None:
            # copy handles launched this step stamp their spans with the
            # iteration id, pairing them with the dispatch window below;
            # the executor's prefill/decode spans carry it too
            self.transfer.trace_iter = it
            self.executor.trace_iter = it

        # ==== LAUNCH phase ==================================================
        # recompute preemption (both pools full): drop KV, requeue
        for r in plan.preempt:
            pool = self.pool.device if r.location == "gpu" else self.pool.host
            pool.free(r.pages)  # refcounted: shared prefix pages survive
            r.pages = []
            r.location = "gpu"
            r.cached_len = 0  # replay re-matches the tree at dispatch
            r.prefix_loc = None
        # the scheduler planned against free + evictable cached pages; evict
        # (demote-first) so the promised room actually exists for the swaps.
        # The gpu pass runs FIRST: it may demote device nodes INTO the host
        # pool, so the host reservation must be carved out afterwards or the
        # demotions would consume the pages the swap-outs are about to alloc.
        if self.prefix_cache is not None:
            need_dev = sum(len(r.pages) for r in plan.swap_in)
            if need_dev:
                self.prefix_cache.make_room("gpu", need_dev)
            need_host = sum(len(r.pages) for r in plan.swap_out)
            if need_host:
                self.prefix_cache.make_room("cpu", need_host)
        # swaps: page accounting moves now; the data moves on the transfer
        # worker (pipelined) or inline (serial)
        out_handles: List = []
        in_handles: List = []
        if pipelined:
            out_handles = [self.transfer.swap_out(r) for r in plan.swap_out]
            in_handles = [self.transfer.swap_in(r) for r in plan.swap_in]
        else:
            for r in plan.swap_out:
                self.pool.swap_request(r, "cpu")
            for r in plan.swap_in:
                self.pool.swap_request(r, "gpu")
        self.scheduler.commit(plan)
        # plan-ahead: speculate the NEXT iteration's plan now, so the planner
        # thread runs under the lane windows dispatched below
        if pipelined and self.engine_cfg.planahead:
            self._launch_planahead(plan)
        dispatch_t0 = time.perf_counter()  # compute-window start (hidden-bytes)

        # ==== DISPATCH phase ================================================
        # Page allocation happens up front, in the SAME order as the serial
        # path (prefill pages, then decode-row pages in gpu/cpu0/cpu1 plan
        # order) — identical page assignment keeps greedy decode bitwise
        # identical.  Replayed prefills (recompute preemption) re-derive
        # their last token deterministically and must not emit it twice.
        page = self._page

        def _running(rs: List[Request]) -> List[Request]:
            return [r for r in rs
                    if r.state == RequestState.RUNNING and r not in plan.prefill]

        rows0 = _running(plan.decode_gpu) + _running(plan.decode_cpu0)
        rows1 = _running(plan.decode_cpu1)
        rows = rows0 + rows1
        host_flags: List[bool] = []

        def _grow_decode_pages() -> None:
            for r in rows:
                host = r.location == "cpu"
                if r.kv_len % page == 0 and r.kv_len // page >= len(r.pages):
                    pool = self.pool.host if host else self.pool.device
                    if self.prefix_cache is not None:
                        self.prefix_cache.make_room("cpu" if host else "gpu", 1)
                    r.pages = r.pages + pool.alloc(1)
                host_flags.append(host)

        if self.prefix_cache is not None:
            # decode rows were budgeted by scheduler step 2, BEFORE prefills
            # (step 3): grow their pages first so a prefill's acquire() pins
            # cannot consume the evictable pages the rows were admitted
            # against (the cache-off path keeps the historical prefill-first
            # allocation order below)
            _grow_decode_pages()

        # Dispatch-time token budget: the scheduler admitted each prefill
        # against its SUBMIT-time cached_len estimate; the authoritative
        # acquire() below may shrink the match (tree changed since submit),
        # growing suffix_len past max_batch_tokens for this one batch.  Page
        # shortfalls already defer — token-budget shortfalls must too.
        token_budget = (self.engine_cfg.max_batch_tokens
                        - len(plan.decode_gpu) - len(plan.decode_cpu0))
        to_host: List[bool] = []
        deferred: List[Request] = []
        for r in plan.prefill:
            host = r in plan.prefill_to_host
            pool = self.pool.host if host else self.pool.device
            # multimodal prompts are not prefix-cached (the partial-prefill
            # path has no extras injection; ROADMAP open item)
            cacheable = (self.prefix_cache is not None
                         and getattr(r, "extras", None) is None)
            if cacheable:
                # authoritative match: pin shared full pages, materialize the
                # COW page for a mid-page hit, then allocate only the suffix
                target = "cpu" if host else "gpu"
                shared, cow, r.cached_len = self.prefix_cache.acquire(
                    r.prefill_tokens, target)
                if r.suffix_len > token_budget:
                    # the match shrank and the realized suffix no longer fits
                    # this batch's token budget: release the pins and defer
                    # to the next iteration.  retract_acquire unwinds the
                    # hit AND the copy counters of the pages just released
                    # (the retry re-runs acquire and would double-count
                    # them); the lookup is dropped too.
                    if shared:
                        pool.free(shared)
                    if cow is not None:
                        pool.free([cow])
                    self.prefix_cache.retract_acquire()
                    self.prefix_cache.retract_lookup(len(r.prefill_tokens))
                    r.cached_len = 0
                    deferred.append(r)
                    continue
                total = -(-r.prefill_len // page)
                fresh = total - len(shared) - (1 if cow is not None else 0)
                self.prefix_cache.make_room(target, fresh)
                if pool.free_pages < fresh:
                    # dispatch-time match exceeded the scheduler's page
                    # budget (tree changed since submit): release the prefix
                    # — the pages stay tree-owned and evictable — and fall
                    # back to a cold prefill under full eviction pressure.
                    # retract_acquire unwinds the hit and the released
                    # copies; the lookup stays (the prompt is still consumed
                    # by the cold path, a genuine miss for hit_rate).
                    if shared:
                        pool.free(shared)
                    if cow is not None:
                        pool.free([cow])
                    self.prefix_cache.retract_acquire()
                    r.cached_len = 0
                    if r.suffix_len > token_budget:
                        # the cold suffix (== full prefill) busts the token
                        # budget too: defer instead of overrunning the batch
                        self.prefix_cache.retract_lookup(len(r.prefill_tokens))
                        deferred.append(r)
                        continue
                    self.prefix_cache.make_room(target, total)
                    if pool.free_pages < total:
                        # genuine overcommit (evictable pages got pinned by
                        # an earlier prefill this step): defer to a later
                        # iteration instead of faulting the whole step; the
                        # retry will re-run acquire, so drop this lookup
                        # from the hit-rate accounting entirely
                        self.prefix_cache.retract_lookup(len(r.prefill_tokens))
                        deferred.append(r)
                        continue
                    r.pages = pool.alloc(total)
                else:
                    r.pages = shared + ([cow] if cow is not None else []) + pool.alloc(fresh)
            else:
                r.cached_len = 0
                npages = -(-r.prefill_len // page)
                if self.prefix_cache is not None:
                    # the scheduler admitted this against free + evictable
                    # tree pages; reclaim them (or defer) before allocating
                    self.prefix_cache.make_room("cpu" if host else "gpu", npages)
                    if pool.free_pages < npages:
                        deferred.append(r)
                        continue
                r.pages = pool.alloc(npages)
            token_budget -= r.suffix_len
            to_host.append(host)
        for r in reversed(deferred):
            # unwind the commit: back to the head of the waitqueue, re-planned
            # next iteration against the true pool state
            plan.prefill.remove(r)
            if r in plan.prefill_to_host:
                plan.prefill_to_host.remove(r)
            if r in self.scheduler.gpu_runq:
                self.scheduler.gpu_runq.remove(r)
            if r in self.scheduler.cpu_runq:
                self.scheduler.cpu_runq.remove(r)
            r.state = RequestState.WAITING
            r.location = "gpu"
            self.scheduler.waitq.appendleft(r)

        if self.prefix_cache is None:
            _grow_decode_pages()  # historical order: prefill pages first

        # ---- unified lane plan -------------------------------------------
        # One optional DEVICE lane (prefill + batch-0's fused graph, engine
        # thread) plus K >= 0 HOST lanes.  The scheduler's ``lane_splits``
        # partition batch-1; preempted rows are filtered per lane
        # (row-independent per-row compute keeps greedy decode bitwise
        # identical under ANY partition — the same padding-bucket invariance
        # the two-batch split relies on).  Host lanes launch FIRST on the
        # executor's lane threads so their lane-scoped swap-out joins + host
        # attention overlap the whole device lane; with no device lane the
        # LAST host lane runs inline on the engine thread (K=1 inline is the
        # serial batch-1 path; K=2 no-device is the PR-3 micro-batch; K>=2
        # WITH a device lane is lane borrowing for mixed plans).
        rows1_ids = set(id(r) for r in rows1)
        lane_rows = [[r for r in lane if id(r) in rows1_ids]
                     for lane in plan.host_lanes()]
        lane_rows = [l for l in lane_rows if l]
        has_dev_lane = bool(plan.prefill or rows0)
        n_lanes = len(lane_rows)
        lane_windows: List[Tuple[float, float]] = []
        futures: List[Tuple[int, Any]] = []
        inline_idx: Optional[int] = None
        if pipelined and lane_rows:
            def _pre(rws: List[Request], li: int):
                # lane-scoped join: the PCIe swap-outs a lane depends on
                # complete right before ITS host attention reads the pages
                return lambda: self.transfer.join_requests(
                    rws, kind="out", track=f"host{li}")
            thread_lanes = lane_rows if has_dev_lane else lane_rows[:-1]
            for li, rws in enumerate(thread_lanes):
                futures.append((li, self.executor.submit_host_lane(
                    rws, pre=_pre(rws, li), lane=li + 1)))
            if not has_dev_lane:
                inline_idx = n_lanes - 1

        # device lane: prefill sub-batch, then batch-0's fused decode graph.
        # Each dispatch's (start, end) window is kept separately so overlap
        # accounting excludes the engine-thread gap between them (joins,
        # prefill emits) — the device is idle there.
        dev_windows: List[Tuple[float, float]] = []
        if plan.prefill:
            slots0 = self.executor.prefill_slot_tokens
            t0 = time.perf_counter()
            logits = self.executor.prefill(plan.prefill, to_host, self._extras_batch)
            dev_windows.append((t0, time.perf_counter()))
            self.stats.device_busy_time += dev_windows[-1][1] - t0
            self.stats.lane_add("prefill", dev_windows[-1][1] - t0)
            if tr is not None:
                tr.emit("device", "prefill", t0, dev_windows[-1][1],
                        {"iter": it, "rows": len(plan.prefill)})
                for r in plan.prefill:
                    tr.async_begin(r.rid, "prefill", t=t0)
                    tr.async_end(r.rid, "prefill", t=dev_windows[-1][1])
            # computed prefill tokens: prefix-cache hits skip the cached part
            self.stats.prefill_tokens += sum(r.suffix_len for r in plan.prefill)
            self.stats.prefill_slot_tokens += (
                self.executor.prefill_slot_tokens - slots0)
            for i, r in enumerate(plan.prefill):
                if not r.out_tokens:
                    self._emit(r, logits[i], now, emitted)

        if rows:
            if pipelined:
                # swap-ins join here, before batch-0's graph consumes (and
                # donates) the pool; swap-outs join lane-scoped on the lane
                # threads
                self.transfer.join(in_handles)
                logits0 = None
                if rows0:
                    t0 = time.perf_counter()
                    logits0 = self.executor.decode_batch0(
                        rows0, host_flags[: len(rows0)])
                    dev_windows.append((t0, time.perf_counter()))
                    self.stats.device_busy_time += dev_windows[-1][1] - t0
                    self.stats.lane_add("batch0", dev_windows[-1][1] - t0)
                    if tr is not None:
                        tr.emit("device", "batch0", t0, dev_windows[-1][1],
                                {"iter": it, "rows": len(rows0)})
                lane_windows = [(0.0, 0.0)] * n_lanes
                lane_logits: List[Optional[np.ndarray]] = [None] * n_lanes
                inline_hb = 0.0
                if inline_idx is not None:
                    # engine-thread lane (no device lane to run instead)
                    rws = lane_rows[inline_idx]
                    self.transfer.join_requests(rws, kind="out")
                    hb0 = self.host_attn.busy_time
                    t0b = time.perf_counter()
                    lane_logits[inline_idx] = self.executor.decode_host_lane(
                        rws, lane=inline_idx + 1)
                    lane_windows[inline_idx] = (t0b, time.perf_counter())
                    inline_hb = self.host_attn.busy_time - hb0
                for li, fut in futures:
                    lane_logits[li], lane_windows[li] = fut.result()
                row_logits: List[np.ndarray] = []
                if rows0:
                    row_logits.extend(np.asarray(logits0))
                for lg in lane_logits:
                    row_logits.extend(np.asarray(lg))
                # ---- measured overlap, generalized to N lanes ------------
                # Each lane contributes its dispatch window(s); realized
                # overlap is the lane-busy time beyond the union span, ideal
                # is everything but the longest lane (perfect packing hides
                # all of it).  For one device lane + one host lane this
                # reduces exactly to the pairwise window intersection.
                for li, w in enumerate(lane_windows):
                    self.stats.lane_add(f"host{li}", w[1] - w[0])
                    if tr is not None:
                        a: Dict[str, Any] = {"iter": it,
                                             "rows": len(lane_rows[li])}
                        if li == inline_idx:
                            a["inline"] = True
                            a["host_busy"] = inline_hb
                        tr.emit(f"host{li}", "lane", w[0], w[1], a)
                interval_lanes: List[List[Tuple[float, float]]] = []
                if dev_windows:
                    interval_lanes.append(list(dev_windows))
                interval_lanes += [[w] for w in lane_windows]
                busy = [sum(e - s for s, e in lw) for lw in interval_lanes]
                if len(interval_lanes) >= 2:
                    merged = sorted(w for lw in interval_lanes for w in lw)
                    union = 0.0
                    cur_s, cur_e = merged[0]
                    for s, e in merged[1:]:
                        if s > cur_e:
                            union += cur_e - cur_s
                            cur_s, cur_e = s, e
                        else:
                            cur_e = max(cur_e, e)
                    union += cur_e - cur_s
                    total = sum(busy)
                    self.stats.pipeline_overlap_time += max(0.0, total - union)
                    self.stats.pipeline_ideal_time += max(
                        0.0, total - max(busy))
                    self.stats.pipelined_steps += 1
                    if n_lanes >= 2:
                        if has_dev_lane:
                            self.stats.borrowed_lane_steps += 1
                        else:
                            self.stats.microbatched_steps += 1
                elif inline_idx is not None:
                    # fully serialized batch-1-only step: the hideable half
                    # (the shorter of host attention vs the linear
                    # remainder) counts as ideal-but-unrealized overlap so
                    # bubble_fraction reflects the missing lane
                    lane_t = busy[0]
                    self.stats.pipeline_ideal_time += max(
                        0.0, min(inline_hb, lane_t - inline_hb))
                    self.stats.serial_b1_steps += 1
                if n_lanes:
                    # K-histogram records the EXECUTED lane count: n_lanes is
                    # derived from lane_rows AFTER the preemption/state
                    # filter, so a plan whose lanes were emptied between
                    # plan and launch (mid-dispatch serial fallback) counts
                    # under the K it actually ran with, not the planned K —
                    # bench_trend publishes this histogram.
                    self.stats.lane_counts[n_lanes] = (
                        self.stats.lane_counts.get(n_lanes, 0) + 1)
            else:
                t0 = time.perf_counter()
                logits = self.executor.decode(rows, host_flags)
                dev_windows.append((t0, time.perf_counter()))
                self.stats.device_busy_time += dev_windows[-1][1] - t0
                self.stats.lane_add("serial", dev_windows[-1][1] - t0)
                if tr is not None:
                    tr.emit("device", "serial", t0, dev_windows[-1][1],
                            {"iter": it, "rows": len(rows)})
                row_logits = list(logits)

            self.stats.offloaded_decodes += sum(host_flags)
            self.stats.device_decodes += len(rows) - sum(host_flags)
            for i, r in enumerate(rows):
                self._emit(r, row_logits[i], now, emitted)

            # speculative draft -> verify -> accept (deferred verification):
            # runs AFTER the base emission so the chain's first pass scores
            # the token just emitted, and BEFORE the JOIN drain so rolled
            # back pages return to the pool within this step
            if plan.spec_k > 0 and self.drafter is not None:
                self._run_spec_chain(plan, rows, now, emitted)

        # ==== JOIN phase ====================================================
        # barrier on any transfer not consumed by a dependent dispatch (e.g.
        # gpu_only swap-outs whose victims do not decode this iteration) so
        # every step ends with pools fully consistent
        if pipelined:
            self.transfer.drain()  # traced: a swap.join span
            # bytes hidden under compute: copy-window overlap with this
            # step's dispatch window (page-table building + prefill + both
            # decode lanes)
            dev_end = dev_windows[-1][1] if dev_windows else None
            lanes_end = max((w[1] for w in lane_windows), default=None)
            win_end = max(filter(None, (dev_end, lanes_end)), default=None)
            if win_end is not None:
                if tr is not None:
                    tr.emit("engine", "dispatch", dispatch_t0, win_end,
                            {"iter": it})
                for h in out_handles + in_handles:
                    self.stats.swap_hidden_bytes += h.hidden_bytes(
                        dispatch_t0, win_end)

    # -- speculative decoding (draft -> verify -> accept) ----------------------
    def _run_spec_chain(self, plan: BatchPlan, rows: List[Request], now: float,
                        emitted: List[Tuple[int, int]]) -> None:
        """Batched draft verification over this step's decode rows in ONE
        extra pass of the UNCHANGED fused decode graph.

        Each speculated row expands into ``len(drafts) + 1`` pseudo-rows
        (:class:`_SpecRow`) at successive KV positions over the row's own
        page table: pseudo-row 0 feeds the base token the step just emitted
        (its logits are the next serial token — a free "bonus" even for rows
        that drafted nothing), pseudo-row ``j >= 1`` feeds draft ``D_{j-1}``
        at position ``kv_len + j``.  One batched :meth:`PagedExecutor.decode`
        call then verifies every draft position at once — the graph writes
        ALL rows' new KV before attention within each layer (device: scatter
        precedes ``paged_decode_attention``; host: ``append_tokens`` precedes
        the per-row attention loop), so pseudo-row ``j`` attends over the
        fresh KV of pseudo-rows ``< j`` exactly as serial decode would.
        Pseudo-row ``j``'s logits are bitwise the serial logits at that
        position PROVIDED the shallower feeds all matched the serial tokens
        — which is precisely the accept condition walked below — so every
        emitted token equals what non-speculative greedy decode would have
        produced, by construction.  One dispatch per step (instead of one
        per accepted token) is where the throughput win comes from: at
        decode batch sizes the pass cost is dominated by fixed dispatch
        overhead, not by the extra pseudo-rows.

        Rollback invariants:

        * ``out_tokens`` is only ever appended to by :meth:`_emit` in the
          accept walk — drafts are fed through detached pseudo-rows, never
          through the row itself — so a rejection leaves the row exactly at
          its serial state.  The rejected tail's KV sits at positions
          ``>= kv_len`` — unread by attention, overwritten by the next
          serial feed at the same slot, and never adopted by the prefix
          cache (adoption stops at ``kv_len``).
        * Pages are rolled back only past the count the row held BEFORE the
          chain, so a prefix-cache-shared page a sibling still references is
          structurally untouchable; chain-grown pages are fresh ``alloc``'d
          refcount-1 pages by definition.
        * Pool exhaustion during up-front growth caps that row's draft depth
          to the positions its pages cover (a row whose base write position
          cannot be covered rides plain decode this step instead).

        Verify wall time accrues to ``spec_busy_time`` and the ``spec``
        span track only — device/lane busy time and the reconcile() audit
        are untouched.
        """
        if self.engine_cfg.decode_sample != "greedy":
            return
        page = self._page
        cand = [r for r in rows
                if r.state == RequestState.RUNNING and not r.is_done()]
        if not cand:
            return
        cand_drafts: Dict[int, List[int]] = {}
        for r in cand:
            cap = min(plan.spec_k, r.max_new_tokens - len(r.out_tokens) - 1)
            cand_drafts[r.rid] = (
                list(self.drafter.propose(r.all_tokens, cap)[:cap])
                if cap > 0 else [])
        if not any(cand_drafts.values()):
            return  # nothing drafted anywhere: skip the verify pass entirely
        # Up-front page growth: a depth-d row writes KV at positions
        # kv_len .. kv_len + d, so it needs (kv_len + d) // page + 1 pages.
        # Exhaustion caps the depth to covered positions; surplus pages roll
        # back in the accept walk.
        erows: List[Request] = []
        drafts: List[List[int]] = []
        base_pages: List[int] = []
        for r in cand:
            d = cand_drafts[r.rid]
            host = r.location == "cpu"
            pool = self.pool.host if host else self.pool.device
            pre = len(r.pages)
            need = (r.kv_len + len(d)) // page + 1
            while len(r.pages) < need:
                if self.prefix_cache is not None:
                    self.prefix_cache.make_room("cpu" if host else "gpu", 1)
                if pool.free_pages < 1:
                    break
                r.pages = r.pages + pool.alloc(1)
            max_depth = len(r.pages) * page - 1 - r.kv_len
            if max_depth < 0:
                continue  # base write position uncovered: plain decode
            erows.append(r)
            drafts.append(d[:max_depth])
            base_pages.append(pre)
        if not erows:
            return
        tr = self.tracer
        t0 = time.perf_counter()
        eflags = [r.location == "cpu" for r in erows]
        prows: List[_SpecRow] = []
        pflags: List[bool] = []
        starts: List[int] = []
        for i, r in enumerate(erows):
            starts.append(len(prows))
            for j, tok in enumerate([r.all_tokens[-1]] + drafts[i]):
                prows.append(_SpecRow(tok, r.kv_len + j, r.pages))
                pflags.append(eflags[i])
        logits = np.asarray(self.executor.decode(prows, pflags))
        # ---- accept walk: emit serially from the verified logits ----------
        drafted = sum(len(d) for d in drafts)
        accepted_total = 0
        for i, r in enumerate(erows):
            d = drafts[i]
            acc = 0
            for j in range(len(d) + 1):
                if r.is_done():
                    break
                self._emit(r, logits[starts[i] + j], now, emitted)
                tok = r.out_tokens[-1]
                if j < len(d):
                    if tok == d[j]:
                        acc += 1
                    else:
                        break  # the emitted token IS the serial correction
            accepted_total += acc
            if d:
                self.stats.accept_len_hist[acc] = (
                    self.stats.accept_len_hist.get(acc, 0) + 1)
            # roll back pages grown past the final KV coverage; never below
            # the pre-chain count (shared prefix pages live there)
            need = max(base_pages[i], r.kv_len // page + 1)
            if len(r.pages) > need:
                extra = r.pages[need:]
                r.pages = r.pages[:need]
                pool = self.pool.host if eflags[i] else self.pool.device
                pool.free(extra)
        t1 = time.perf_counter()
        self.stats.spec_steps += 1
        self.stats.drafted_tokens += drafted
        self.stats.accepted_tokens += accepted_total
        self.stats.rejected_drafts += drafted - accepted_total
        self.stats.spec_busy_time += t1 - t0
        self.perf.observe_accept(drafted, accepted_total)
        if tr is not None:
            tr.emit("spec", "verify", t0, t1,
                    {"iter": self.stats.iterations, "k": plan.spec_k,
                     "rows": len(erows), "pseudo_rows": len(prows),
                     "drafted": drafted, "accepted": accepted_total})

    # -- contiguous families ---------------------------------------------------
    def _step_contiguous(self, plan: BatchPlan, now: float, emitted: List[Tuple[int, int]]) -> None:
        self.scheduler.commit(plan)
        for r in plan.prefill:
            slot = self.executor.alloc_slot()
            r.pages = [slot]
            extras = getattr(r, "extras", None)
            if extras:
                extras = {k: jnp.asarray(v)[None] for k, v in extras.items()}
            logits = self.executor.prefill(r, slot, extras)
            self.stats.prefill_tokens += r.prompt_len
            self.stats.prefill_slot_tokens += r.prefill_len  # B=1, no pad
            self._emit(r, logits, now, emitted)
        rows = [r for r in plan.decode_rows if r.state == RequestState.RUNNING
                and r not in plan.prefill]
        if rows:
            tokens_by_slot = np.zeros((self.executor.slots,), np.int32)
            for r in rows:
                tokens_by_slot[r.pages[0]] = r.all_tokens[-1]
            logits = self.executor.decode(tokens_by_slot)
            self.stats.device_decodes += len(rows)
            for r in rows:
                self._emit(r, logits[r.pages[0]], now, emitted)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def close(self) -> None:
        """Join and stop the background transfer/dispatch/planner threads.

        Idempotent; a transfer that failed in flight surfaces its error
        here, but only after every worker pool has been torn down.
        """
        self._spec = None
        if self._planner is not None:
            self._planner.shutdown(wait=True)
            self._planner = None
        try:
            if self.transfer is not None:
                self.transfer.close()
                # the closing drain's joins, as a step's mirror would count
                self.stats.swap_wait_time = self.transfer.stats.wait_time
        finally:
            if self.paged:
                self.executor.close()

    # ------------------------------------------------------------------
    # drivers
    # ------------------------------------------------------------------
    def run_until_done(self, max_iters: int = 10_000) -> Dict[int, List[int]]:
        """Drain all queued work; returns {rid: out_tokens}."""
        it = 0
        while self.scheduler.num_queued > 0 and it < max_iters:
            self.step(now=self.clock + 1e-3)
            it += 1
        return {rid: list(r.out_tokens) for rid, r in self.requests.items()}

    # ------------------------------------------------------------------
    # fault tolerance: journal + prefill-replay recovery
    # ------------------------------------------------------------------
    def export_journal(self) -> List[Dict[str, Any]]:
        out = []
        for e in self._journal:
            req = self.requests[e["rid"]]
            out.append(
                {
                    **{k: v for k, v in e.items() if k != "out_tokens"},
                    "out_tokens": list(req.out_tokens),
                    "finished": req.state in (RequestState.FINISHED, RequestState.ABORTED),
                }
            )
        return out

    def replay_journal(self, journal: List[Dict[str, Any]]) -> Dict[int, int]:
        """Resume unfinished journaled requests on THIS engine (prefill-replay).

        Returns {old_rid: new_rid}.  Emitted tokens are preserved by extending
        the replay prompt; generation continues from the exact next position.
        """
        mapping: Dict[int, int] = {}
        for e in journal:
            if e.get("finished"):
                continue
            done = len(e["out_tokens"])
            if done >= e["max_new_tokens"]:
                continue
            new_rid = self.submit(
                list(e["prompt"]) + list(e["out_tokens"]),
                e["max_new_tokens"] - done,
                arrival_time=e.get("arrival_time", 0.0),
                eos_token=e.get("eos_token"),
            )
            mapping[e["rid"]] = new_rid
        return mapping
