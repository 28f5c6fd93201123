"""Paged dual-pool KV cache: device (HBM) pool + host (DRAM) pool.

Layout per pool: K and V arrays of shape ``[L, P, page, KV, hd]`` — page-major
so a page is one contiguous DMA unit (the swap granularity).  The device pool
is a jax array; the host pool is numpy (it stands for pinned host memory on a
real TPU VM; the host attention kernel reads it directly).

Free-page accounting is host-side (Python) exactly like vLLM's block manager.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ArchConfig


class PagePool:
    """One pool (device or host) with a free list."""

    def __init__(
        self,
        cfg: ArchConfig,
        num_pages: int,
        *,
        backend: str,  # "device" | "host"
        num_layers: Optional[int] = None,
        dtype=None,
        mesh=None,
    ):
        self.cfg = cfg
        self.backend = backend
        self.page_size = cfg.kv_block_size
        self.num_pages = num_pages
        L = num_layers if num_layers is not None else cfg.num_attention_layers
        self.num_layers = L
        shape = (L, num_pages, self.page_size, cfg.num_kv_heads, cfg.head_dim)
        self.dtype = dtype or (np.float32 if cfg.activation_dtype == "float32" else jnp.bfloat16)
        self.mesh = mesh
        if backend == "device":
            self.k = jnp.zeros(shape, self.dtype)
            self.v = jnp.zeros(shape, self.dtype)
            if mesh is not None and mesh.shape.get("model", 1) > 1:
                # Tensor-parallel serving: the device pool shards by KV head
                # over the "model" axis while the page-id space — the free
                # list, refcounts, Request.pages and the prefix-cache radix
                # tree above it — stays GLOBAL: every shard holds the same
                # pages, each covering its own KV-head slice.
                from jax.sharding import NamedSharding, PartitionSpec as _P

                sh = NamedSharding(mesh, _P(None, None, None, "model", None))
                self.k = jax.device_put(self.k, sh)
                self.v = jax.device_put(self.v, sh)
        else:
            # The host pool holds the device pool's dtype: 16-bit archs store
            # bfloat16 (ml_dtypes' numpy dtype, 2 bytes/elt), so swaps and
            # prefill placement move the device's bits without a cast and
            # host rows read exactly the values device rows read; float32
            # archs keep float32.  HostAttention widens bf16 per task.
            self.k = np.zeros(shape, self.dtype)
            self.v = np.zeros(shape, self.dtype)
        self._free: List[int] = list(range(num_pages))
        # Per-page reference counts (prefix-cache sharing): a page returns to
        # the free list only when its LAST reader releases it.  Unshared pages
        # keep the historical alloc/free semantics (ref 1 -> 0).
        self._ref: List[int] = [0] * num_pages
        # Optional refcount-transition listener ``fn(page, old, new)``: the
        # prefix cache registers one to maintain its incremental evictability
        # counters — pin/unpin events (1<->2 crossings) on tree-owned pages
        # happen through engine-side incref/free calls the cache never sees.
        self._ref_listener: Optional[Callable[[int, int, int], None]] = None

    def set_ref_listener(self, fn: Optional[Callable[[int, int, int], None]]) -> None:
        self._ref_listener = fn

    # -- accounting ------------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"{self.backend} pool out of pages: want {n}, have {len(self._free)}"
            )
        pages, self._free = self._free[:n], self._free[n:]
        for p in pages:
            self._ref[p] = 1
        return pages

    def incref(self, pages: List[int]) -> None:
        """Add a reader to already-allocated (shared) pages."""
        for p in pages:
            if self._ref[p] <= 0:
                raise ValueError(f"incref of free page {p}")
            self._ref[p] += 1
            if self._ref_listener is not None:
                self._ref_listener(p, self._ref[p] - 1, self._ref[p])

    def refcount(self, page: int) -> int:
        return self._ref[page]

    def free(self, pages: List[int]) -> None:
        """Release one reference per page; pages with no remaining readers
        return to the free list (a double release raises)."""
        if len(set(pages)) != len(pages):
            raise ValueError("duplicate pages in free()")
        for p in pages:
            assert 0 <= p < self.num_pages
            if self._ref[p] <= 0:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
            if self._ref_listener is not None:
                self._ref_listener(p, self._ref[p] + 1, self._ref[p])

    # -- device pool writes (jit'd) --------------------------------------------
    def write_decode_tokens(self, layer_kv: Tuple[jnp.ndarray, jnp.ndarray],
                            layer: int, page_ids: jnp.ndarray, offsets: jnp.ndarray,
                            valid: jnp.ndarray) -> None:
        """Write one token per row into device pool pages.

        layer_kv: (k, v) each [R, KV, hd]; page_ids/offsets/valid: [R].
        """
        assert self.backend == "device"
        k_new, v_new = layer_kv
        self.k = _scatter_tokens(self.k, k_new, layer, page_ids, offsets, valid)
        self.v = _scatter_tokens(self.v, v_new, layer, page_ids, offsets, valid)

    def write_prefill_pages(self, layer: int, page_ids: np.ndarray,
                            k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                            valid: np.ndarray) -> None:
        """Write whole pages: k_pages [NPg, page, KV, hd]; page_ids/valid [NPg]."""
        assert self.backend == "device"
        self.k = _scatter_pages(self.k, k_pages, layer, jnp.asarray(page_ids), jnp.asarray(valid))
        self.v = _scatter_pages(self.v, v_pages, layer, jnp.asarray(page_ids), jnp.asarray(valid))

    # -- host pool writes (numpy) ------------------------------------------------
    def write_host_pages(self, layer: int, page_ids: np.ndarray,
                         k_pages: np.ndarray, v_pages: np.ndarray,
                         valid: np.ndarray) -> None:
        assert self.backend == "host"
        ids = page_ids[valid]
        self.k[layer, ids] = k_pages[valid]
        self.v[layer, ids] = v_pages[valid]

    def write_host_tokens(self, layer: int, page_ids: np.ndarray, offsets: np.ndarray,
                          k_new: np.ndarray, v_new: np.ndarray, valid: np.ndarray) -> None:
        assert self.backend == "host"
        ids, offs = page_ids[valid], offsets[valid]
        self.k[layer, ids, offs] = k_new[valid]
        self.v[layer, ids, offs] = v_new[valid]

    def write_token_range(self, page_ids: np.ndarray, offsets: np.ndarray,
                          k_toks, v_toks) -> None:
        """Write per-token KV across ALL layers: k_toks/v_toks [L, T, KV, hd]
        land at (page_ids[t], offsets[t]).  Used by the suffix-prefill path to
        fill a copy-on-write page from an arbitrary token offset."""
        if self.backend == "device":
            ids = jnp.asarray(page_ids, jnp.int32)
            offs = jnp.asarray(offsets, jnp.int32)
            self.k = self.k.at[:, ids, offs].set(jnp.asarray(k_toks, self.k.dtype))
            self.v = self.v.at[:, ids, offs].set(jnp.asarray(v_toks, self.v.dtype))
        else:
            self.k[:, page_ids, offsets] = np.asarray(k_toks, self.k.dtype)
            self.v[:, page_ids, offsets] = np.asarray(v_toks, self.v.dtype)

    # -- per-shard host views (TP host attention) -------------------------------
    def kv_head_slice(self, shard: int, num_shards: int) -> Tuple[np.ndarray, np.ndarray]:
        """Writable numpy VIEWS of this host pool covering shard ``shard``'s
        KV heads — per-shard :class:`HostAttention` instances read and append
        through these, so the host tier stays ONE allocation (single NUMA
        node, §5.1) with a single global page-id space."""
        assert self.backend == "host"
        KV = self.k.shape[3]
        if KV % num_shards != 0:
            raise ValueError(
                f"{KV} kv heads do not divide across {num_shards} shards")
        per = KV // num_shards
        lo = shard * per
        return (self.k[:, :, :, lo:lo + per, :],
                self.v[:, :, :, lo:lo + per, :])

    # -- swap I/O ---------------------------------------------------------------
    def read_pages(self, pages: List[int]) -> Tuple[np.ndarray, np.ndarray]:
        """[L, n, page, KV, hd] numpy copies (device→host PCIe DMA analogue)."""
        idx = np.asarray(pages, np.int32)
        if self.backend == "device":
            return np.asarray(self.k[:, idx]), np.asarray(self.v[:, idx])
        return self.k[:, idx].copy(), self.v[:, idx].copy()

    def put_pages(self, pages: List[int], k_np: np.ndarray, v_np: np.ndarray) -> None:
        idx = np.asarray(pages, np.int32)
        if self.backend == "device":
            self.k = self.k.at[:, idx].set(jnp.asarray(k_np, self.k.dtype))
            self.v = self.v.at[:, idx].set(jnp.asarray(v_np, self.v.dtype))
        else:
            self.k[:, idx] = k_np
            self.v[:, idx] = v_np


@jax.jit
def _scatter_tokens(pool, new, layer, page_ids, offsets, valid):
    # pool: [L, P, page, KV, hd]; new: [R, KV, hd]
    safe_pid = jnp.where(valid, page_ids, 0)
    safe_off = jnp.where(valid, offsets, 0)
    cur = pool[layer, safe_pid, safe_off]
    upd = jnp.where(valid[:, None, None], new.astype(pool.dtype), cur)
    return pool.at[layer, safe_pid, safe_off].set(upd)


@jax.jit
def _scatter_pages(pool, pages_data, layer, page_ids, valid):
    # pool: [L, P, page, KV, hd]; pages_data: [NPg, page, KV, hd]
    safe = jnp.where(valid, page_ids, 0)
    cur = pool[layer, safe]
    upd = jnp.where(valid[:, None, None, None], pages_data.astype(pool.dtype), cur)
    return pool.at[layer, safe].set(upd)


class DualPool:
    """Device + host pools plus whole-request swap (the scheduler's swap-in/out)."""

    def __init__(self, cfg: ArchConfig, device_pages: int, host_pages: int,
                 *, mesh=None):
        self.cfg = cfg
        self.page_size = cfg.kv_block_size
        self.mesh = mesh
        self.device = PagePool(cfg, device_pages, backend="device", mesh=mesh)
        self.host = PagePool(cfg, host_pages, backend="host")
        # PCIe traffic accounting — updated from the engine thread (prefill
        # host writes, serial swaps) and the transfer worker; lock-protected
        self.swap_bytes = 0
        self._swap_lock = threading.Lock()

    def add_swap_bytes(self, n: int) -> None:
        with self._swap_lock:
            self.swap_bytes += n

    def pool(self, location: str) -> PagePool:
        return self.device if location == "gpu" else self.host

    def swap_request(self, req, to: str) -> None:
        """Move a request's whole KV between pools. ``to``: "gpu" | "cpu".

        Blocking whole-request copy — the serial execution path.  The
        pipelined engine uses :class:`repro.core.transfer.TransferEngine`
        instead, which overlaps these copies with compute.
        """
        src = self.device if to == "cpu" else self.host
        dst = self.host if to == "cpu" else self.device
        if not req.pages:
            req.location = "gpu" if to == "gpu" else "cpu"
            return
        k_np, v_np = src.read_pages(req.pages)
        new_pages = dst.alloc(len(req.pages))
        dst.put_pages(new_pages, k_np, v_np)
        src.free(req.pages)
        req.pages = new_pages
        req.location = "gpu" if to == "gpu" else "cpu"
        self.add_swap_bytes(k_np.nbytes + v_np.nbytes)
