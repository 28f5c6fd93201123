"""Execution backends for the NEO engine (§3.1 asymmetric pipelining).

Two executors:

* :class:`PagedExecutor` — dense / moe / vlm families.  Decode runs over the
  paged dual-pool KV cache in two separately dispatched sub-batches:

  - **batch-0** (device rows + ``cpu0`` host rows): ONE jitted graph per
    (rows, pages) bucket — device rows attend via the paged-attention kernel
    (the compiled Pallas kernel on TPU, the jnp
    reference on the CPU); its host rows detour through an
    **ordered io_callback** to :class:`HostAttention` per layer (the
    JAX-native analogue of the paper's TrQKV → CPU-attn → TrO pipeline).
    Python kernel-launch overhead is paid once per iteration (the paper's §4
    launch-overhead fix, achieved with XLA fusion instead of CUDA C++).
  - **host lanes** (batch-1 rows): fused host-only graphs — small jitted
    linear stages plus :meth:`HostAttention.run_layer` through a per-lane
    ordered io_callback chain.  Because they never touch the device KV
    pool, any number of lanes run **concurrently** with each other and with
    batch-0's jitted dispatch; :meth:`submit_host_lane` hands each lane's
    result back through a future (Fig. 5's asymmetric overlap, realized
    rather than modelled).  The engine maps the scheduler's unified lane
    plan onto them: K=1 is the classic batch-1 hiding under batch-0, K>=2
    with no batch-0 is the FastDecode-style micro-batch split, and K>=2
    WITH a (short, decode-only) batch-0 is lane borrowing — the surplus
    host rows overlap the device lane AND each other.  Each lane owns its
    own io_callback/state/fused-graph triple, so concurrent graphs never
    share mutable state.

  The serial :meth:`decode` path (all rows in one fused graph) is kept for
  ``pipeline=False`` and as the bitwise-equality oracle for the pipelined
  path.

* :class:`ContiguousExecutor` — ssm / hybrid / audio families (and any arch
  with ``supports_offload=False``).  Slot-based contiguous caches driven by
  the model's own prefill/decode; device-only scheduling (NEO's degradation
  mode — there is no growing KV to offload).
"""

from __future__ import annotations

import functools
import time
import weakref
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import ArchConfig
from repro.core.host_attention import HostAttention
from repro.core.kv_cache import DualPool
from repro.core.request import Request
from repro.distributed.sharding import (
    ShardingContext,
    activate,
    gather_tp_spec,
    tp_allgather,
    tp_axis,
    tp_body,
)
from repro.kernels.paged_decode import ops as paged_ops
from repro.models.layers import embed_lookup, logits_last, rms_norm, swiglu_apply
from repro.models.moe import moe_apply
from repro.models.transformer import DenseLM, project_qkv

Params = Dict[str, Any]


def _bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _weak(method, *first):
    """``method``, a bound method, as an io_callback target that holds its
    object weakly, with ``first`` put before the callback's arguments.

    JAX's tracing caches keep every callback target of a traced graph for
    the life of the process; a bound method there would keep the executor,
    and through it the weights and the pools, on the device after its
    engine is gone."""
    ref = weakref.WeakMethod(method)

    def call(*args):
        return ref()(*first, *args)
    return call


def _suffix_bucket(reqs: List[Request]) -> int:
    """Token bucket S of a cached-prefill graph over ``reqs``' suffixes."""
    return _bucket(max(r.suffix_len for r in reqs), 16)


class PagedExecutor:
    """Paged decode + bucketed prefill for decoder-only attention families."""

    def __init__(self, model: DenseLM, params: Params, pool: DualPool,
                 host_attn: HostAttention, *, impl: Optional[str] = None,
                 host_lanes: int = 2, tp: int = 1, mesh=None):
        self.model = model
        self.cfg: ArchConfig = model.cfg
        self.params = params
        self.pool = pool
        self.host = host_attn
        # Device decode attention: the platform's implementation unless a
        # test overrides it; "interpret" runs the Pallas kernel in interpret
        # mode (CPU tests only).
        self.interpret = impl == "interpret"
        self.impl = "pallas" if self.interpret else (
            impl or paged_ops.platform_impl())
        self.page = pool.page_size
        # --- gather-TP (reduction-free tensor parallelism) ---------------
        # Column-shard QKV / MLP-up over the mesh "model" axis, keep O /
        # down / embeddings replicated, and concat shard partials with a
        # tiled all_gather before every replicated contraction — greedy
        # decode stays BITWISE identical to the single-device graphs.  The
        # scheduler / lane-plan layers above stay device-count-agnostic:
        # only the fused graphs, the device page pool and the host-attention
        # callbacks here know the shard count.
        self.tp = max(1, int(tp))
        self.mesh = mesh
        self.host_shards: List[HostAttention] = []
        if self.tp > 1:
            cfg = self.cfg
            if mesh is None:
                raise ValueError("tp > 1 requires a device mesh")
            if cfg.moe is not None or cfg.modality is not None:
                raise NotImplementedError(
                    "tensor-parallel serving covers the dense family only")
            if (cfg.num_heads % self.tp or cfg.num_kv_heads % self.tp
                    or cfg.d_ff % self.tp):
                raise ValueError(
                    f"tp={self.tp} must divide num_heads={cfg.num_heads}, "
                    f"num_kv_heads={cfg.num_kv_heads} and d_ff={cfg.d_ff}")
            self.tp_ctx: Optional[ShardingContext] = ShardingContext.for_arch(
                cfg, mesh)
            axes = model.param_logical_axes()
            self._tp_param_specs = jax.tree.map(
                gather_tp_spec, axes, is_leaf=lambda t: isinstance(t, tuple))
            # self.params stays single-device: host lanes and the gathered
            # prefix-prefill path run the unsharded graphs unchanged.
            self.params_tp = jax.tree.map(
                lambda leaf, sp: jax.device_put(leaf, NamedSharding(mesh, sp)),
                params, self._tp_param_specs)
            # One HostAttention per shard over a writable kv-head slice of
            # the SAME host pool allocation — page ids stay global, only the
            # head axis is partitioned (host attention shards by KV head).
            for s in range(self.tp):
                k_s, v_s = pool.host.kv_head_slice(s, self.tp)
                self.host_shards.append(
                    HostAttention(cfg, k_s, v_s, threads=host_attn.threads))
        else:
            self.tp_ctx = None
            self._tp_param_specs = None
            self.params_tp = None
        # per-iteration host-side state consumed by the io_callback
        self._cb_state: Dict[str, np.ndarray] = {}
        self._decode_fns: Dict[Tuple[int, int], Any] = {}
        self._prefill_fns: Dict[Tuple[int, int], Any] = {}
        # Host lanes: up to ``host_lanes`` dispatch threads plus per-lane
        # fused host-only graphs, each with a SEPARATE io_callback/state
        # pair so concurrent graphs never share mutable state.  Lane ids are
        # small ints assigned by the engine per step; lane 1 doubles as the
        # classic batch-1 lane (K=1 plans), and for batch-1-only plans the
        # engine runs the LAST lane inline on its own thread (the engine
        # thread would otherwise idle) while the rest dispatch here.
        self.host_lanes = max(1, host_lanes)
        self._lane_pool = ThreadPoolExecutor(max_workers=self.host_lanes,
                                             thread_name_prefix="neo-hostlane")
        self._cb_lane_state: Dict[int, Dict[str, np.ndarray]] = {}
        self._lane_fns: Dict[int, Any] = {}
        # zero-copy host-prefix prefill: per-dispatch state for the ordered
        # prefix-partials callback (engine thread only; lane callbacks own
        # their separate per-lane state dicts)
        self._cb_prefix_state: Dict[str, np.ndarray] = {}
        # tracing (repro.obs): set by the engine when EngineConfig.tracing
        # is on; host-attention callbacks and lane threads emit spans, and
        # prefill/decode steps on the engine thread carry ``trace_iter``
        self.tracer = None
        self.trace_iter = 0
        # padded tokens of every prefill graph run (B x S), beside the
        # engine's computed ``prefill_tokens``; the engine mirrors it
        self.prefill_slot_tokens = 0

    # ------------------------------------------------------------------
    # host attention callback (one per layer, ordered)
    # ------------------------------------------------------------------
    def _host_cb(self, layer, q, k_new, v_new):
        st = self._cb_state
        layer = int(layer)
        if st["host_rows"].size == 0:
            return np.zeros(q.shape, np.float32)
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        out = self.host.run_layer(
            layer,
            np.asarray(q),
            np.asarray(k_new),
            np.asarray(v_new),
            host_rows=st["host_rows"],
            tables=st["tables"],
            lens=st["lens"],
            page_ids=st["page_ids"],
            offsets=st["offsets"],
            window=int(st["window"][0]) if "window" in st else 0,
        )
        if tr is not None:
            tr.emit("hostattn-b0", f"L{layer}", t0, time.perf_counter(),
                    {"rows": int(st["host_rows"].size),
                     "kernel": self.host.kernel})
        return out

    def _host_cb_tp(self, shard, layer, q, k_new, v_new):
        """Per-shard batch-0 host attention (TP decode; unordered callback).

        ``q``/``k_new``/``v_new`` are the shard's LOCAL head slices; the
        shard's :class:`HostAttention` owns the matching kv-head slice of
        the host pool, so concurrent shard callbacks write disjoint memory
        and keep separate accounting.
        """
        st = self._cb_state
        shard, layer = int(shard), int(layer)
        if st["host_rows"].size == 0:
            return np.zeros(q.shape, np.float32)
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        out = self.host_shards[shard].run_layer(
            layer,
            np.asarray(q),
            np.asarray(k_new),
            np.asarray(v_new),
            host_rows=st["host_rows"],
            tables=st["tables"],
            lens=st["lens"],
            page_ids=st["page_ids"],
            offsets=st["offsets"],
            window=int(st["window"][0]) if "window" in st else 0,
        )
        if tr is not None:
            tr.emit(f"hostattn-b0-s{shard}", f"L{layer}", t0,
                    time.perf_counter(),
                    {"rows": int(st["host_rows"].size), "shard": shard,
                     "kernel": self.host_shards[shard].kernel})
        return out

    # ------------------------------------------------------------------
    # decode step graph
    # ------------------------------------------------------------------
    # The per-layer step is split into pre (norm + QKV projection) and post
    # (output projection + FFN) halves shared VERBATIM by the fused batch-0
    # graph and the batch-1 lane — op-for-op identity is what keeps the
    # pipelined path bitwise equal to the serial one.
    def _layer_pre(self, p: Params, x, positions):
        cfg = self.cfg
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        q, k, v = project_qkv(p["attn"], cfg, h[:, None, :], positions[:, None])
        return q[:, 0], k[:, 0], v[:, 0]  # [D,H,hd], [D,KV,hd]

    def _layer_post(self, kind: str, p: Params, x, o):
        cfg = self.cfg
        # gather-TP seam: concat per-shard head outputs before the
        # replicated wo (identity outside a TP body)
        o = tp_allgather(o, axis=1)
        out = jnp.einsum("bhk,hkd->bd", o, p["attn"]["wo"])
        x = x + out
        h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
        if kind == "moe":
            m, _ = moe_apply(p["moe"], h2[:, None, :], cfg.moe)
            m = m[:, 0]
        else:
            m = swiglu_apply(p["mlp"], h2)
        return x + m

    def _layer_step(self, p: Params, kind: str, lidx, x, pool_k, pool_v,
                    tokens_meta) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        (positions, dev_bt, dev_lens, is_host, page_ids, offsets) = tokens_meta
        q, k, v = self._layer_pre(p, x, positions)

        # -- device pool append (host rows masked out; they go to scratch) ----
        valid = ~is_host
        safe_pid = jnp.where(valid, page_ids, 0)  # page 0 = reserved scratch
        safe_off = jnp.where(valid, offsets, 0)
        cur_k = pool_k[lidx, safe_pid, safe_off]
        cur_v = pool_v[lidx, safe_pid, safe_off]
        upd_k = jnp.where(valid[:, None, None], k.astype(pool_k.dtype), cur_k)
        upd_v = jnp.where(valid[:, None, None], v.astype(pool_v.dtype), cur_v)
        pool_k = pool_k.at[lidx, safe_pid, safe_off].set(upd_k)
        pool_v = pool_v.at[lidx, safe_pid, safe_off].set(upd_v)

        # -- device paged attention (host rows attend over 1 scratch token) ---
        dev_out = paged_ops.paged_decode_attention(
            q, pool_k[lidx], pool_v[lidx], dev_bt, dev_lens + 1,
            impl=self.impl, interpret=self.interpret,
        )
        # -- host attention via ordered callback (TrQKV -> CPU attn -> TrO) ---
        ax = tp_axis()
        if ax is None:
            host_out = io_callback(
                _weak(self._host_cb),
                jax.ShapeDtypeStruct(q.shape, jnp.float32),
                lidx, q, k, v,
                ordered=True,
            )
        else:
            # Per-shard host attention: q/k/v carry the LOCAL head slice
            # and the shard index routes to that shard's HostAttention over
            # its kv-head slice of the host pool.  Cross-layer ordering is
            # carried by the data dependence (x threads through each layer
            # via host_out), so the callback can be unordered — ordered
            # io_callback is not supported inside shard_map bodies.
            sidx = jax.lax.axis_index(ax)
            host_out = io_callback(
                _weak(self._host_cb_tp),
                jax.ShapeDtypeStruct(q.shape, jnp.float32),
                sidx, lidx, q, k, v,
                ordered=False,
            )
        o = jnp.where(is_host[:, None, None], host_out.astype(dev_out.dtype), dev_out)
        return self._layer_post(kind, p, x, o), pool_k, pool_v

    def _decode_graph(self, params, tokens, positions, dev_bt, dev_lens,
                      is_host, page_ids, offsets, pool_k, pool_v):
        """The fused decode step, shared VERBATIM by the single-device jit
        and (wrapped in ``tp_body`` inside a shard_map) the TP builder —
        op-for-op identity is what keeps TP=N bitwise equal to TP=1."""
        model, cfg = self.model, self.cfg
        x = embed_lookup(params["embed"], tokens).astype(cfg.activation_dtype)
        meta = (positions, dev_bt, dev_lens, is_host, page_ids, offsets)
        for i, kind in enumerate(model.prefix_kinds):
            x, pool_k, pool_v = self._layer_step(
                params[f"prefix{i}"], kind, jnp.int32(i), x, pool_k, pool_v, meta
            )
        n_prefix = len(model.prefix_kinds)
        r = len(model.repeat_kinds)

        def group_body(carry, scanned):
            x, pk, pv, base = carry
            gp = scanned
            for j, kind in enumerate(model.repeat_kinds):
                x, pk, pv = self._layer_step(gp[f"sub{j}"], kind, base + j, x, pk, pv, meta)
            return (x, pk, pv, base + r), None

        (x, pool_k, pool_v, _), _ = jax.lax.scan(
            group_body, (x, pool_k, pool_v, jnp.int32(n_prefix)), params["blocks"]
        )
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = logits_last(x, model._unembed(params))
        return logits, pool_k, pool_v

    def _build_decode(self, D: int, MP: int):
        return jax.jit(self._decode_graph, donate_argnums=(8, 9))

    def _build_decode_tp(self, D: int, MP: int):
        """TP decode: ONE jitted shard_map graph over the "model" axis.

        Params enter pre-sharded per :func:`gather_tp_spec`; the device
        pool tiles its kv-head axis; scalar metadata replicates.  The body
        is the exact single-device graph traced under ``tp_body`` so the
        model-level ``tp_allgather`` seams become tiled all_gathers (pure
        concats) and ``shard(...)`` annotations become no-ops.  Logits are
        computed identically on every shard (replicated out-spec).
        """
        kv_spec = P(None, None, None, "model", None)

        def step(params, tokens, positions, dev_bt, dev_lens, is_host,
                 page_ids, offsets, pool_k, pool_v):
            with tp_body("model"):
                return self._decode_graph(params, tokens, positions, dev_bt,
                                          dev_lens, is_host, page_ids,
                                          offsets, pool_k, pool_v)

        wrapped = jax.shard_map(
            step, mesh=self.mesh, check_vma=False,
            in_specs=(self._tp_param_specs, P(), P(), P(), P(), P(), P(), P(),
                      kv_spec, kv_spec),
            out_specs=(P(), kv_spec, kv_spec),
        )
        return jax.jit(wrapped, donate_argnums=(8, 9))

    def decode_fn(self, D: int, MP: int):
        key = (D, MP)
        if key not in self._decode_fns:
            build = self._build_decode_tp if self.tp > 1 else self._build_decode
            self._decode_fns[key] = build(D, MP)
        return self._decode_fns[key]

    # ------------------------------------------------------------------
    # public decode entry
    # ------------------------------------------------------------------
    def decode(self, rows: List[Request], host_flags: List[bool],
               window: int = 0) -> np.ndarray:
        """One decode iteration over ``rows``; returns logits [n_rows, V].

        Page allocation for the new token must already be done (engine).
        """
        n = len(rows)
        D = _bucket(n)
        MP = _bucket(max(
            [len(r.pages) for r, h in zip(rows, host_flags) if not h] + [1]), 4)
        page = self.page

        tokens = np.zeros((D,), np.int32)
        positions = np.zeros((D,), np.int32)
        dev_bt = np.zeros((D, MP), np.int32)
        dev_lens = np.zeros((D,), np.int32)
        is_host = np.ones((D,), bool)  # pad rows behave as host rows w/o work
        page_ids = np.zeros((D,), np.int32)
        offsets = np.zeros((D,), np.int32)

        host_rows, h_tables, h_lens, h_pids, h_offs = [], [], [], [], []
        max_hp = max([len(r.pages) for r, h in zip(rows, host_flags) if h] + [1])
        for i, (r, h) in enumerate(zip(rows, host_flags)):
            pos = r.kv_len  # next position
            tokens[i] = r.all_tokens[-1]
            positions[i] = pos
            pid = r.pages[pos // page]
            off = pos % page
            if h:
                host_rows.append(i)
                tbl = np.zeros((max_hp,), np.int32)
                tbl[: len(r.pages)] = r.pages
                h_tables.append(tbl)
                h_lens.append(pos)
                h_pids.append(pid)
                h_offs.append(off)
            else:
                is_host[i] = False
                dev_bt[i, : len(r.pages)] = r.pages
                dev_lens[i] = pos
                page_ids[i] = pid
                offsets[i] = off

        self._cb_state = {
            "host_rows": np.asarray(host_rows, np.int64),
            "tables": np.asarray(h_tables, np.int32).reshape(len(host_rows), max_hp),
            "lens": np.asarray(h_lens, np.int32),
            "page_ids": np.asarray(h_pids, np.int32),
            "offsets": np.asarray(h_offs, np.int32),
            "window": np.asarray([window], np.int32),
        }
        fn = self.decode_fn(D, MP)
        dev = self.pool.device
        if self.tp > 1:
            with activate(self.tp_ctx):
                logits, dev.k, dev.v = fn(
                    self.params_tp, tokens, positions, dev_bt, dev_lens,
                    is_host, page_ids, offsets, dev.k, dev.v,
                )
        else:
            logits, dev.k, dev.v = fn(
                self.params, tokens, positions, dev_bt, dev_lens, is_host,
                page_ids, offsets, dev.k, dev.v,
            )
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        out = np.asarray(logits[:n])
        if tr is not None:
            # the full-vocab fetch; it waits for the graph to finish
            tr.emit("decode", "decode.logits", t0, time.perf_counter(),
                    {"iter": self.trace_iter, "rows": n, "bytes": out.nbytes})
        return out

    # batch-0 is the fused graph over device + cpu0 rows — exactly the serial
    # entry restricted to its sub-batch.
    decode_batch0 = decode

    # ------------------------------------------------------------------
    # host lanes (host rows only; run off the engine thread)
    # ------------------------------------------------------------------
    def _host_cb_lane(self, lane, layer, q, k_new, v_new):
        st = self._cb_lane_state[lane]
        layer = int(layer)
        if st["host_rows"].size == 0:
            return np.zeros(q.shape, np.float32)
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        out = self.host.run_layer(
            layer,
            np.asarray(q),
            np.asarray(k_new),
            np.asarray(v_new),
            host_rows=st["host_rows"],
            tables=st["tables"],
            lens=st["lens"],
            page_ids=st["page_ids"],
            offsets=st["offsets"],
            window=int(st["window"][0]) if "window" in st else 0,
        )
        if tr is not None:
            tr.emit(f"hostattn-lane{lane}", f"L{layer}", t0,
                    time.perf_counter(), {"rows": int(st["host_rows"].size),
                                          "kernel": self.host.kernel})
        return out

    def _build_decode_lane(self, lane: int):
        """Fused decode graph for an all-host-rows lane: the per-layer pre
        and post halves are shared with the batch-0 graph; attention is the
        ordered host callback only — no device pool access, no donation, so
        the graph can execute concurrently with batch-0's and with every
        other lane's graph.  One jit object per lane; jax retraces per row
        bucket."""
        model, cfg = self.model, self.cfg
        cb = _weak(self._host_cb_lane, lane)

        def layer(p: Params, kind: str, lidx, x, positions):
            q, k, v = self._layer_pre(p, x, positions)
            host_out = io_callback(
                cb,
                jax.ShapeDtypeStruct(q.shape, jnp.float32),
                lidx, q, k, v,
                ordered=True,
            )
            # same cast the batch-0 graph applies to host rows (pool dtype ==
            # activation dtype)
            o = host_out.astype(cfg.activation_dtype)
            return self._layer_post(kind, p, x, o)

        def step(params, tokens, positions):
            x = embed_lookup(params["embed"], tokens).astype(cfg.activation_dtype)
            for i, kind in enumerate(model.prefix_kinds):
                x = layer(params[f"prefix{i}"], kind, jnp.int32(i), x, positions)
            n_prefix = len(model.prefix_kinds)
            r = len(model.repeat_kinds)

            def group_body(carry, gp):
                x, base = carry
                for j, kind in enumerate(model.repeat_kinds):
                    x = layer(gp[f"sub{j}"], kind, base + j, x, positions)
                return (x, base + r), None

            (x, _), _ = jax.lax.scan(
                group_body, (x, jnp.int32(n_prefix)), params["blocks"]
            )
            x = rms_norm(x, params["final_norm"], cfg.rms_eps)
            return logits_last(x, model._unembed(params))

        return jax.jit(step)

    def decode_lane_fn(self, lane: int = 1):
        if lane not in self._lane_fns:
            self._lane_fns[lane] = self._build_decode_lane(lane)
        return self._lane_fns[lane]

    def decode_host_lane(self, rows: List[Request], window: int = 0,
                         *, lane: int = 1) -> np.ndarray:
        """One decode iteration over host-resident ``rows`` (one host lane).

        One fused jitted dispatch whose per-layer host attention (append new
        KV token + attend over the host pool) runs through its OWN ordered
        callback chain on :class:`HostAttention`.  Never touches the device
        KV pool, so it is safe to run concurrently with
        :meth:`decode_batch0` and with any other host lane — that
        concurrency is the lane overlap of Fig. 5, generalized to N lanes.
        ``lane`` selects an independent callback/state/graph triple; each
        concurrently dispatching caller thread must use a distinct lane id.
        """
        n = len(rows)
        D = _bucket(n)
        page = self.page
        tokens = np.zeros((D,), np.int32)
        positions = np.zeros((D,), np.int32)
        max_hp = max(len(r.pages) for r in rows)
        tables = np.zeros((n, max_hp), np.int32)
        lens = np.zeros((n,), np.int32)
        pids = np.zeros((n,), np.int32)
        offs = np.zeros((n,), np.int32)
        for i, r in enumerate(rows):
            pos = r.kv_len
            tokens[i] = r.all_tokens[-1]
            positions[i] = pos
            tables[i, : len(r.pages)] = r.pages
            lens[i] = pos
            pids[i] = r.pages[pos // page]
            offs[i] = pos % page
        self._cb_lane_state[lane] = {
            "host_rows": np.arange(n, dtype=np.int64),
            "tables": tables,
            "lens": lens,
            "page_ids": pids,
            "offsets": offs,
            "window": np.asarray([window], np.int32),
        }
        logits = self.decode_lane_fn(lane)(self.params, tokens, positions)
        return np.asarray(logits[:n])

    # ------------------------------------------------------------------
    # pipelined dispatch (futures-based handoff)
    # ------------------------------------------------------------------
    def submit_host_lane(
        self,
        rows: List[Request],
        window: int = 0,
        *,
        pre: Optional[Callable[[], None]] = None,
        lane: int = 1,
    ) -> Future:
        """Launch one host lane on a dispatch thread; the future resolves to
        ``(logits [n,V], (start, end))`` perf_counter stamps.

        ``pre`` runs on the lane thread before any page is read — the
        engine passes the lane-scoped swap-out join there (traced on this
        lane's ``host<lane-1>`` track), so PCIe transfers complete exactly
        when (and only when) the dependent host attention needs them.
        """

        def run_lane() -> Tuple[np.ndarray, Tuple[float, float]]:
            t0 = time.perf_counter()
            if pre is not None:
                pre()  # traced: a swap.join span on this lane's host track
            out = self.decode_host_lane(rows, window, lane=lane)
            return out, (t0, time.perf_counter())

        return self._lane_pool.submit(run_lane)

    def close(self) -> None:
        self._lane_pool.shutdown(wait=True)

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def _build_prefill(self, B: int, S: int):
        model = self.model

        def fn(params, tokens, true_lens, extras):
            logits, cache = model.prefill(
                params, tokens, capacity=S, true_lens=true_lens, **extras
            )
            return logits, cache["k"], cache["v"]

        return jax.jit(fn)

    def _build_prefill_tp(self, B: int, S: int):
        """TP cold prefill: the same model.prefill traced per shard under
        ``tp_body`` inside a shard_map — the cache comes back tiled on its
        kv-head axis (matching the device pool layout) and the first-token
        logits replicated (identical per shard by construction)."""
        model = self.model
        kv_spec = P(None, None, None, "model", None)

        def body(params, tokens, true_lens):
            with tp_body("model"):
                logits, cache = model.prefill(
                    params, tokens, capacity=S, true_lens=true_lens
                )
                return logits, cache["k"], cache["v"]

        wrapped = jax.shard_map(
            body, mesh=self.mesh, check_vma=False,
            in_specs=(self._tp_param_specs, P(), P()),
            out_specs=(P(), kv_spec, kv_spec),
        )
        return jax.jit(wrapped)

    def prefill_fn(self, B: int, S: int):
        key = (B, S)
        if key not in self._prefill_fns:
            build = self._build_prefill_tp if self.tp > 1 else self._build_prefill
            self._prefill_fns[key] = build(B, S)
        return self._prefill_fns[key]

    def prefill(self, reqs: List[Request], to_host: List[bool],
                extras_fn=None) -> np.ndarray:
        """Prefill ``reqs`` (bucketed padding), scatter KV into the pools.

        Pages must already be allocated on ``req.pages`` in the right pool.
        Requests with a prefix-cache hit (``cached_len > 0``) take the
        partial-prefill path — only the suffix is computed, attending over
        the cached prefix pages; the rest go through the cold path unchanged.
        Returns first-token logits [n, V].
        """
        warm_idx = [i for i, r in enumerate(reqs) if r.cached_len > 0]
        if warm_idx:
            warm_set = set(warm_idx)
            cold_idx = [i for i in range(len(reqs)) if i not in warm_set]
            warm_logits = self._prefill_cached(
                [reqs[i] for i in warm_idx], [to_host[i] for i in warm_idx])
            out = np.zeros((len(reqs), warm_logits.shape[-1]), np.float32)
            out[warm_idx] = np.asarray(warm_logits, np.float32)
            if cold_idx:
                cold_logits = self._prefill_cold(
                    [reqs[i] for i in cold_idx], [to_host[i] for i in cold_idx],
                    extras_fn)
                out[cold_idx] = np.asarray(cold_logits, np.float32)
            return out
        return self._prefill_cold(reqs, to_host, extras_fn)

    def _prefill_cold(self, reqs: List[Request], to_host: List[bool],
                      extras_fn=None) -> np.ndarray:
        """Traced, on the ``prefill`` track: ``prefill.graph`` (inputs and
        dispatch), one ``prefill.place`` per request (pad, reshape and the
        pool write or the host copy) and ``prefill.logits`` (the fetch)."""
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        n = len(reqs)
        S = _bucket(max(r.prefill_len for r in reqs), 16)
        B = n
        page = self.page
        self.prefill_slot_tokens += B * S
        tokens = np.zeros((B, S), np.int32)
        lens = np.zeros((B,), np.int32)
        for i, r in enumerate(reqs):
            tokens[i, : r.prefill_len] = r.prefill_tokens
            lens[i] = r.prefill_len
        extras = extras_fn(reqs, S) if extras_fn else {}
        if self.tp > 1:
            if extras:
                raise NotImplementedError("prefill extras unsupported at tp>1")
            with activate(self.tp_ctx):
                logits, k_all, v_all = self.prefill_fn(B, S)(
                    self.params_tp, tokens, lens
                )
        else:
            logits, k_all, v_all = self.prefill_fn(B, S)(
                self.params, tokens, lens, extras
            )
        if tr is not None:
            tr.emit("prefill", "prefill.graph", t0, time.perf_counter(),
                    {"iter": self.trace_iter, "B": B, "S": S})
        # scatter into pools, page-granular (device) / numpy (host)
        for i, (r, host) in enumerate(zip(reqs, to_host)):
            p0 = time.perf_counter() if tr is not None else 0.0
            npages = len(r.pages)
            S_pad = npages * page
            kr = k_all[:, i]
            vr = v_all[:, i]
            if S_pad > S:
                padw = [(0, 0), (0, S_pad - S), (0, 0), (0, 0)]
                kr, vr = jnp.pad(kr, padw), jnp.pad(vr, padw)
            else:
                kr, vr = kr[:, :S_pad], vr[:, :S_pad]
            kr = kr.reshape(kr.shape[0], npages, page, *kr.shape[2:])
            vr = vr.reshape(vr.shape[0], npages, page, *vr.shape[2:])
            if host:
                k_host, v_host = np.asarray(kr), np.asarray(vr)
                self.pool.host.put_pages(r.pages, k_host, v_host)
                # layer-wise PCIe swap of the freshly computed KV
                self.pool.add_swap_bytes(k_host.nbytes + v_host.nbytes)
            else:
                self.pool.device.put_pages(r.pages, kr, vr)
            if tr is not None:
                tr.emit("prefill", "prefill.place", p0, time.perf_counter(),
                        {"iter": self.trace_iter, "rid": r.rid,
                         "pages": npages, "to_host": bool(host),
                         "bytes": kr.nbytes + vr.nbytes})
        return self._prefill_logits(logits, "prefill")

    def _prefill_logits(self, logits, track: str) -> np.ndarray:
        """The first-token logits fetch that ends every prefill."""
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        out = np.asarray(logits)
        if tr is not None:
            tr.emit(track, "prefill.logits", t0, time.perf_counter(),
                    {"iter": self.trace_iter, "bytes": out.nbytes})
        return out

    # ------------------------------------------------------------------
    # partial prefill over a cached prefix (prefix cache)
    # ------------------------------------------------------------------
    def _build_prefill_prefix(self, B: int, S: int, T: int):
        model, cfg = self.model, self.cfg

        def fn(params, tokens, true_lens, prefix_k, prefix_v, prefix_lens):
            pk = prefix_k.astype(cfg.activation_dtype)
            pv = prefix_v.astype(cfg.activation_dtype)
            return model.prefill_with_prefix(
                params, tokens, pk, pv, prefix_lens,
                capacity=S, true_lens=true_lens,
            )

        return jax.jit(fn)

    def prefill_prefix_fn(self, B: int, S: int, T: int):
        key = ("prefix", B, S, T)
        if key not in self._prefill_fns:
            self._prefill_fns[key] = self._build_prefill_prefix(B, S, T)
        return self._prefill_fns[key]

    def _prefill_cached(self, reqs: List[Request], to_host: List[bool]) -> np.ndarray:
        """Suffix-only prefill for prefix-cache hits.

        ``req.pages`` already holds the shared/COW prefix pages (in the
        target pool) followed by freshly allocated suffix pages.  Rows land
        on one of two paths:

        * **device rows** gather the cached prefix KV from the device pool
          into a padded [L, B, T, KV, hd] graph input (the PR-2 path);
        * **host rows** take the ZERO-COPY host-serving path — the prefix
          stays in the host pool and each layer's suffix queries detour
          through an ordered callback computing flash partials over the
          in-place pages (:meth:`HostAttention.prefix_partials`), so the
          prefix never crosses PCIe; only the freshly computed suffix KV is
          written back.

        Both scatter the suffix KV token-granular (the COW page fills from
        a mid-page offset).
        """
        host_idx = [i for i, h in enumerate(to_host) if h]
        gpu_idx = [i for i, h in enumerate(to_host) if not h]
        for leg in (host_idx, gpu_idx):  # each leg's graph: B x S slots
            if leg:
                self.prefill_slot_tokens += len(leg) * _suffix_bucket(
                    [reqs[i] for i in leg])
        if host_idx and gpu_idx:
            # the two legs touch disjoint rows and pools: run the CPU-heavy
            # host-partials leg on a lane thread so it overlaps the device
            # gather graph instead of stalling the device lane (same
            # concurrency contract as decode_host_lane — the host-prefix
            # graph never touches the device KV pool)
            fut = self._lane_pool.submit(
                self._prefill_cached_host, [reqs[i] for i in host_idx],
                "prefill-lane")
            out_g = self._prefill_cached_gather([reqs[i] for i in gpu_idx])
            out_h = fut.result()
            out = np.zeros((len(reqs), out_h.shape[-1]), np.float32)
            out[host_idx] = out_h
            out[gpu_idx] = out_g
            return out
        if host_idx:
            return self._prefill_cached_host(reqs)
        return self._prefill_cached_gather(reqs)

    def _scatter_suffix(self, reqs: List[Request], suffix_lens: np.ndarray,
                        k_all, v_all, to_host: bool, track: str) -> None:
        """Token-granular suffix-KV scatter: the suffix starts at offset
        ``cached_len``, which may sit mid-page (inside the COW page).
        Traced: one ``prefill.place`` span per request on ``track``."""
        page, cfg = self.page, self.cfg
        L = self.pool.host.num_layers
        KV, hd = cfg.num_kv_heads, cfg.head_dim
        pool = self.pool.host if to_host else self.pool.device
        tr = self.tracer
        for i, r in enumerate(reqs):
            p0 = time.perf_counter() if tr is not None else 0.0
            suf = int(suffix_lens[i])
            pos = r.cached_len + np.arange(suf)
            pids = np.asarray([r.pages[p // page] for p in pos], np.int32)
            offs = (pos % page).astype(np.int32)
            pool.write_token_range(pids, offs, k_all[:, i, :suf], v_all[:, i, :suf])
            nb = 2 * suf * L * KV * hd * pool.k.dtype.itemsize
            if to_host:  # layer-wise PCIe swap of the freshly computed KV
                self.pool.add_swap_bytes(nb)
            if tr is not None:
                tr.emit(track, "prefill.place", p0, time.perf_counter(),
                        {"iter": self.trace_iter, "rid": r.rid,
                         "pages": len(set(pids.tolist())),
                         "to_host": bool(to_host), "bytes": nb})

    def _prefill_cached_gather(self, reqs: List[Request]) -> np.ndarray:
        """Device rows: gather the cached prefix into the prefix-attention
        graph input, then scatter the suffix KV into the device pool.
        Traced like the cold path (the prefix gather is part of
        ``prefill.graph``)."""
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        cfg, page = self.cfg, self.page
        n = len(reqs)
        L = self.pool.device.num_layers
        KV, hd = cfg.num_kv_heads, cfg.head_dim
        S = _suffix_bucket(reqs)
        t_pages = _bucket(max(-(-r.cached_len // page) for r in reqs), 1)
        T = t_pages * page

        tokens = np.zeros((n, S), np.int32)
        suffix_lens = np.zeros((n,), np.int32)
        prefix_lens = np.zeros((n,), np.int32)
        pre_k = np.zeros((L, n, T, KV, hd), np.float32)
        pre_v = np.zeros((L, n, T, KV, hd), np.float32)
        for i, r in enumerate(reqs):
            suf = r.suffix_len
            tokens[i, :suf] = r.prefill_tokens[r.cached_len:]
            suffix_lens[i] = suf
            prefix_lens[i] = r.cached_len
            npg = -(-r.cached_len // page)
            k_np, v_np = self.pool.device.read_pages(r.pages[:npg])
            pre_k[:, i, : npg * page] = k_np.reshape(L, npg * page, KV, hd)
            pre_v[:, i, : npg * page] = v_np.reshape(L, npg * page, KV, hd)

        logits, k_all, v_all = self.prefill_prefix_fn(n, S, T)(
            self.params, tokens, suffix_lens, pre_k, pre_v, prefix_lens
        )
        if self.tp > 1:
            # this path runs the unsharded graph on the default device; the
            # suffix KV must cross to numpy (uncommitted) before the scatter
            # into the mesh-sharded device pool
            k_all = np.asarray(k_all)
            v_all = np.asarray(v_all)
        if tr is not None:
            tr.emit("prefill", "prefill.graph", t0, time.perf_counter(),
                    {"iter": self.trace_iter, "B": n, "S": S, "T": T})
        self._scatter_suffix(reqs, suffix_lens, k_all, v_all, to_host=False,
                             track="prefill")
        return self._prefill_logits(logits, "prefill")

    # -- zero-copy host-prefix path ------------------------------------------
    def _host_prefix_cb(self, layer, q):
        st = self._cb_prefix_state
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        out = self.host.prefix_partials(
            int(layer), np.asarray(q), st["tables"], st["prefix_lens"])
        if tr is not None:
            tr.emit("hostattn-prefix", f"L{int(layer)}", t0,
                    time.perf_counter(), {"rows": int(st["tables"].shape[0])})
        return out

    def _host_prefix_cb_tp(self, shard, layer, q):
        """Per-shard zero-copy prefix partials (TP host-prefix prefill).

        ``q`` is the shard's LOCAL query-head slice; the shard's
        :class:`HostAttention` reads its kv-head slice of the host pool in
        place, and the per-shard LSE partials merge on device via
        ``suffix_attention_merge`` before the head all_gather.
        """
        st = self._cb_prefix_state
        shard = int(shard)
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        out = self.host_shards[shard].prefix_partials(
            int(layer), np.asarray(q), st["tables"], st["prefix_lens"])
        if tr is not None:
            tr.emit(f"hostattn-prefix-s{shard}", f"L{int(layer)}", t0,
                    time.perf_counter(),
                    {"rows": int(st["tables"].shape[0]), "shard": shard})
        return out

    def _build_prefill_host_prefix(self, B: int, S: int):
        model = self.model

        def fn(params, tokens, true_lens, prefix_lens):
            return model.prefill_with_host_prefix(
                params, tokens, prefix_lens, prefix_cb=_weak(self._host_prefix_cb),
                capacity=S, true_lens=true_lens,
            )

        return jax.jit(fn)

    def _build_prefill_host_prefix_tp(self, B: int, S: int):
        """TP host-prefix prefill: per-shard suffix graphs whose prefix
        partials come from the shard's HostAttention (sharded by KV head)
        through an unordered per-shard callback."""
        model = self.model
        kv_spec = P(None, None, None, "model", None)

        def body(params, tokens, true_lens, prefix_lens):
            with tp_body("model"):
                return model.prefill_with_host_prefix(
                    params, tokens, prefix_lens,
                    prefix_cb=_weak(self._host_prefix_cb_tp),
                    capacity=S, true_lens=true_lens,
                )

        wrapped = jax.shard_map(
            body, mesh=self.mesh, check_vma=False,
            in_specs=(self._tp_param_specs, P(), P(), P()),
            out_specs=(P(), kv_spec, kv_spec),
        )
        return jax.jit(wrapped)

    def prefill_host_prefix_fn(self, B: int, S: int):
        key = ("hostprefix", B, S)
        if key not in self._prefill_fns:
            build = (self._build_prefill_host_prefix_tp if self.tp > 1
                     else self._build_prefill_host_prefix)
            self._prefill_fns[key] = build(B, S)
        return self._prefill_fns[key]

    def _prefill_cached_host(self, reqs: List[Request],
                             track: str = "prefill") -> np.ndarray:
        """Host rows: ZERO-COPY host serving.  The cached prefix pages stay
        in the host pool and are read in place, at their absolute positions,
        by the per-layer prefix-partials callback; only the computed suffix
        KV crosses PCIe (the writeback into the host pool).  Traced on
        ``track`` (the calling thread's): ``prefill.graph`` also covers the
        graph's drain and the suffix-KV fetch."""
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        page = self.page
        n = len(reqs)
        S = _suffix_bucket(reqs)
        max_pp = max(-(-r.cached_len // page) for r in reqs)
        tokens = np.zeros((n, S), np.int32)
        suffix_lens = np.zeros((n,), np.int32)
        prefix_lens = np.zeros((n,), np.int32)
        tables = np.zeros((n, max_pp), np.int32)
        for i, r in enumerate(reqs):
            suf = r.suffix_len
            tokens[i, :suf] = r.prefill_tokens[r.cached_len:]
            suffix_lens[i] = suf
            prefix_lens[i] = r.cached_len
            npg = -(-r.cached_len // page)
            tables[i, :npg] = r.pages[:npg]
        self._cb_prefix_state = {"tables": tables, "prefix_lens": prefix_lens}
        if self.tp > 1:
            with activate(self.tp_ctx):
                logits, k_all, v_all = self.prefill_host_prefix_fn(n, S)(
                    self.params_tp, tokens, suffix_lens, prefix_lens
                )
        else:
            logits, k_all, v_all = self.prefill_host_prefix_fn(n, S)(
                self.params, tokens, suffix_lens, prefix_lens
            )
        # Drain the callback-bearing graph with a plain wait BEFORE
        # dispatching anything that depends on its outputs.  Slicing
        # k_all/v_all while this graph is still in flight enqueues new
        # executables through the runtime's dispatch path; the ordered
        # per-layer prefix callback needs that same path to materialize its
        # operands, and on low-core hosts the two deadlock (main thread in
        # write_token_range materializing a slice, callback thread stuck on
        # np.asarray(q) forever).  block_until_ready takes no dispatch
        # locks, and the numpy conversion afterwards makes the scatter pure
        # host-side work.
        jax.block_until_ready((logits, k_all, v_all))
        k_all = np.asarray(k_all)
        v_all = np.asarray(v_all)
        if tr is not None:
            tr.emit(track, "prefill.graph", t0, time.perf_counter(),
                    {"iter": self.trace_iter, "B": n, "S": S})
        self._scatter_suffix(reqs, suffix_lens, k_all, v_all, to_host=True,
                             track=track)
        return self._prefill_logits(logits, track)


# ---------------------------------------------------------------------------
# Contiguous slot executor (ssm / hybrid / audio; device-only)
# ---------------------------------------------------------------------------


class ContiguousExecutor:
    """Slot-based contiguous-cache executor driven by the model's own
    prefill/decode.  One slot per active request; decode steps all slots."""

    def __init__(self, model, params: Params, *, slots: int, capacity: int):
        self.model = model
        self.cfg: ArchConfig = model.cfg
        self.params = params
        self.slots = slots
        self.capacity = capacity
        self.cache = model.init_cache(slots, capacity)
        self._batch_axes = self._find_batch_axes()
        self.free_slots = list(range(slots))
        self._decode_jit = jax.jit(
            lambda p, t, c, w: model.decode(p, t, c, window=w),
            static_argnums=(3,),
        )
        self._prefill_jits: Dict[int, Any] = {}
        self._insert_jit = jax.jit(self._insert, donate_argnums=(0,), static_argnums=())

    def _find_batch_axes(self) -> Dict[str, int]:
        shapes = self.model.cache_shape(self.slots, self.capacity)
        out = {}
        for name, (shp, dt, axes) in shapes.items():
            out[name] = axes.index("batch")
        return out

    # -- slot management ------------------------------------------------------
    def alloc_slot(self) -> int:
        return self.free_slots.pop(0)

    def free_slot(self, s: int) -> None:
        self.free_slots.insert(0, s)

    def _insert(self, cache, one, slot):
        new = {}
        for name, leaf in cache.items():
            ax = self._batch_axes[name]
            src = one[name]
            if src.shape[ax] == 1:
                src = src[(slice(None),) * ax + (0,)]  # drop batch dim
            # zero-pad variable-size dims (e.g. encoder memory) to slot shape
            tgt_shape = leaf.shape[:ax] + leaf.shape[ax + 1:]
            if src.shape != tgt_shape:
                pad = [(0, t - s) for s, t in zip(src.shape, tgt_shape)]
                src = jnp.pad(src, pad)
            idx = [slice(None)] * leaf.ndim
            idx[ax] = slot
            new[name] = leaf.at[tuple(idx)].set(src)
        return new

    # -- serve ------------------------------------------------------------
    def prefill(self, req: Request, slot: int, extras: Optional[Dict] = None) -> np.ndarray:
        S = req.prefill_len
        if S not in self._prefill_jits:
            self._prefill_jits[S] = jax.jit(
                functools.partial(self.model.prefill, capacity=self.capacity)
            )
        tokens = jnp.asarray([req.prefill_tokens], jnp.int32)
        logits, one = self._prefill_jits[S](self.params, tokens, **(extras or {}))
        self.cache = self._insert_jit(self.cache, one, slot)
        return np.asarray(logits[0])

    def decode(self, tokens_by_slot: np.ndarray, window: int = 0) -> np.ndarray:
        logits, self.cache = self._decode_jit(
            self.params, jnp.asarray(tokens_by_slot, jnp.int32), self.cache, window
        )
        return np.asarray(logits)
