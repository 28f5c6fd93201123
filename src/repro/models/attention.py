"""Attention paths.

Three implementations, all numerically equivalent (tested against each other):

* :func:`chunked_attention` — train/prefill full attention, flash-style
  chunking over the query dimension so the [S, S] score matrix is never fully
  materialised.  Used inside ``lax.scan`` over layers; sharding-annotated.
* :func:`decode_attention` — one-token decode over a contiguous per-sequence
  KV cache (the jitted at-scale serve path; "heads"/"replicated" KV layouts).
* :func:`decode_attention_blocksharded` — split-K decode via ``shard_map``
  over the "model" mesh axis for archs whose KV-head count does not divide
  the axis (KV *pages* shard instead; partial-softmax psum combine).  This is
  the cross-chip analogue of the paper's Flash-Decoding-style CPU kernel.

The paged-pool variants used by the NEO engine live in
``repro.kernels.paged_decode`` (Pallas kernel + jnp oracle).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import shard
from repro.distributed.sharding import current_context

NEG_INF = -1e30


def _repeat_kv(k: jnp.ndarray, q_per_kv: int) -> jnp.ndarray:
    """[..., KV, hd] -> [..., KV*q_per_kv, hd] (each kv head repeated)."""
    if q_per_kv == 1:
        return k
    return jnp.repeat(k, q_per_kv, axis=-2)


def _heads_sharded() -> bool:
    ctx = current_context()
    return ctx is not None and ctx.rules.get("heads") is not None


# ---------------------------------------------------------------------------
# Train / prefill attention (chunked over queries)
# ---------------------------------------------------------------------------


def chunked_attention(
    q: jnp.ndarray,  # [B, Sq, H, hd]
    k: jnp.ndarray,  # [B, Skv, KV, hd]
    v: jnp.ndarray,  # [B, Skv, KV, hd]
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 1024,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Full (optionally causal / sliding-window) attention, chunked over Sq.

    ``q_offset`` is the absolute position of q[:, 0] relative to k[:, 0]
    (used by chunked prefill continuation).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    q_per_kv = H // k.shape[2]
    scale = 1.0 / math.sqrt(hd)

    k = _repeat_kv(k, q_per_kv)  # [B, Skv, H, hd]
    v = _repeat_kv(v, q_per_kv)
    if _heads_sharded():
        k = shard(k, "batch", None, "heads", None)
        v = shard(v, "batch", None, "heads", None)
    else:
        k = shard(k, "batch", "kv_seq", None, None)
        v = shard(v, "batch", "kv_seq", None, None)

    q_chunk = min(q_chunk, Sq)
    while Sq % q_chunk != 0:  # Sq is a power-of-two in every assigned shape
        q_chunk //= 2
    n_chunks = Sq // q_chunk
    qc = q.reshape(B, n_chunks, q_chunk, H, hd).swapaxes(0, 1)  # [n, B, c, H, hd]
    kv_pos = jnp.arange(Skv)

    def one_chunk(args):
        qi, ci = args  # [B, c, H, hd], scalar chunk index
        q_pos = q_offset + ci * q_chunk + jnp.arange(q_chunk)
        s = jnp.einsum("bchd,bshd->bchs", qi, k).astype(jnp.float32) * scale
        if _heads_sharded():
            s = shard(s, "batch", None, "heads", None)
        else:
            s = shard(s, "batch", None, None, "kv_seq")
        mask = jnp.ones((q_chunk, Skv), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window:
            mask &= q_pos[:, None] - kv_pos[None, :] < window
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bchs,bshd->bchd", p.astype(v.dtype), v)
        return o

    if n_chunks == 1:
        out = one_chunk((qc[0], jnp.int32(0)))[None]
    else:
        out = jax.lax.map(one_chunk, (qc, jnp.arange(n_chunks, dtype=jnp.int32)))
    out = out.swapaxes(0, 1).reshape(B, Sq, H, hd)
    if _heads_sharded():
        out = shard(out, "batch", None, "heads", None)
    return out


# ---------------------------------------------------------------------------
# Decode attention over a contiguous per-sequence cache
# ---------------------------------------------------------------------------


def decode_attention(
    q: jnp.ndarray,  # [B, H, hd]
    k_cache: jnp.ndarray,  # [B, S, KV, hd] (already includes the new token)
    v_cache: jnp.ndarray,
    lens: jnp.ndarray,  # [B] int32 — number of valid tokens (incl. new one)
    *,
    window: int = 0,
) -> jnp.ndarray:
    B, S, KV, hd = k_cache.shape
    H = q.shape[1]
    q_per_kv = H // KV
    scale = 1.0 / math.sqrt(hd)

    kr = _repeat_kv(k_cache, q_per_kv)  # [B, S, H, hd]
    vr = _repeat_kv(v_cache, q_per_kv)
    s = jnp.einsum("bhd,bshd->bhs", q, kr).astype(jnp.float32) * scale
    pos = jnp.arange(S)
    mask = pos[None, :] < lens[:, None]
    if window:
        mask &= pos[None, :] >= (lens[:, None] - window)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p.astype(vr.dtype), vr)


# ---------------------------------------------------------------------------
# Suffix-prefill attention over a cached prefix (prefix cache partial prefill)
# ---------------------------------------------------------------------------


def prefix_attention(
    q: jnp.ndarray,  # [B, S, H, hd] — suffix queries
    k_pre: jnp.ndarray,  # [B, T, KV, hd] — cached prefix KV, padded to T
    v_pre: jnp.ndarray,
    prefix_lens: jnp.ndarray,  # [B] int32 — valid prefix tokens per row
    k_new: jnp.ndarray,  # [B, S, KV, hd] — the suffix's own KV
    v_new: jnp.ndarray,
) -> jnp.ndarray:
    """Attention for a partial prefill starting at a nonzero KV offset.

    Query ``i`` of row ``b`` sits at absolute position ``prefix_lens[b] + i``
    and attends over the row's valid cached prefix plus the suffix causally.
    Prefix padding beyond ``prefix_lens`` (and jointly, via one softmax over
    the concatenated score matrix) is masked out.  Returns [B, S, H, hd].
    """
    B, S, H, hd = q.shape
    T = k_pre.shape[1]
    q_per_kv = H // k_new.shape[2]
    scale = 1.0 / math.sqrt(hd)

    kp = _repeat_kv(k_pre, q_per_kv)
    vp = _repeat_kv(v_pre, q_per_kv)
    kn = _repeat_kv(k_new, q_per_kv)
    vn = _repeat_kv(v_new, q_per_kv)

    s_pre = jnp.einsum("bqhd,bkhd->bqhk", q, kp).astype(jnp.float32) * scale
    s_new = jnp.einsum("bqhd,bkhd->bqhk", q, kn).astype(jnp.float32) * scale
    pre_valid = jnp.arange(T)[None, :] < prefix_lens[:, None]  # [B, T]
    s_pre = jnp.where(pre_valid[:, None, None, :], s_pre, NEG_INF)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]  # [S, S]
    s_new = jnp.where(causal[None, :, None, :], s_new, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([s_pre, s_new], axis=-1), axis=-1)
    o = jnp.einsum("bqhk,bkhd->bqhd", p[..., :T].astype(vp.dtype), vp)
    o = o + jnp.einsum("bqhk,bkhd->bqhd", p[..., T:].astype(vn.dtype), vn)
    return o


def suffix_attention_merge(
    q: jnp.ndarray,  # [B, S, H, hd] — suffix queries
    k_new: jnp.ndarray,  # [B, S, KV, hd] — the suffix's own KV
    v_new: jnp.ndarray,
    pre_acc: jnp.ndarray,  # [B, S, H, hd] — prefix flash partials (host)
    pre_l: jnp.ndarray,  # [B, S, H]
    pre_m: jnp.ndarray,  # [B, S, H]; <= -1e30 marks "no prefix" rows
) -> jnp.ndarray:
    """Partial prefill where the PREFIX attention was computed elsewhere.

    The zero-copy host-serving path of :func:`prefix_attention`: instead of
    gathering the cached prefix KV into device arrays, the host computes
    flash partials ``(acc, l, m)`` over its in-place prefix pages and only
    those cross back; this function computes the causal suffix
    self-attention on device and log-sum-exp-combines the two — numerically
    the joint softmax over [prefix, causal suffix].  Returns [B, S, H, hd]
    float32.
    """
    B, S, H, hd = q.shape
    q_per_kv = H // k_new.shape[2]
    scale = 1.0 / math.sqrt(hd)
    kn = _repeat_kv(k_new, q_per_kv)
    vn = _repeat_kv(v_new, q_per_kv)
    s_new = jnp.einsum("bqhd,bkhd->bqhk", q, kn).astype(jnp.float32) * scale
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]  # [S, S]
    s_new = jnp.where(causal[None, :, None, :], s_new, NEG_INF)
    m_new = jnp.max(s_new, axis=-1)  # [B, S, H]; diagonal keeps it finite
    e = jnp.exp(s_new - m_new[..., None])
    l_new = jnp.sum(e, axis=-1)
    acc_new = jnp.einsum("bqhk,bkhd->bqhd", e.astype(vn.dtype), vn).astype(jnp.float32)
    m_tot = jnp.maximum(pre_m, m_new)
    c_pre = jnp.exp(pre_m - m_tot)  # 0 where there is no prefix
    c_new = jnp.exp(m_new - m_tot)
    num = pre_acc * c_pre[..., None] + acc_new * c_new[..., None]
    den = pre_l * c_pre + l_new * c_new
    return num / jnp.maximum(den, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# Split-K decode attention, KV pages sharded over the "model" axis
# ---------------------------------------------------------------------------


def _partial_flash(q, k_local, v_local, valid_mask, scale):
    """Unnormalised local attention: returns (acc [B,H,hd], l [B,H], m [B,H])."""
    s = jnp.einsum("bhd,bshd->bhs", q, k_local).astype(jnp.float32) * scale
    s = jnp.where(valid_mask[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B, H]
    # Shards with no valid key: keep m finite so exp() stays well-defined.
    m_safe = jnp.maximum(m, NEG_INF / 2)
    e = jnp.exp(s - m_safe[..., None])
    e = jnp.where(valid_mask[:, None, :], e, 0.0)
    l = jnp.sum(e, axis=-1)
    acc = jnp.einsum("bhs,bshd->bhd", e.astype(v_local.dtype), v_local).astype(jnp.float32)
    return acc, l, m_safe


def decode_attention_blocksharded(
    q: jnp.ndarray,  # [B, H, hd]
    k_cache: jnp.ndarray,  # [B, S, KV, hd]; S sharded over "model"
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, KV, hd] — token to insert at position lens
    v_new: jnp.ndarray,
    lens: jnp.ndarray,  # [B] int32 — tokens valid BEFORE the insert
    *,
    window: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Insert (k_new, v_new) at position ``lens`` and attend over lens+1 keys.

    KV sequence is sharded over the "model" mesh axis; each shard computes a
    partial flash-attention over its local chunk and the result is combined
    with a log-sum-exp psum — the cross-chip analogue of split-K Flash
    Decoding (and of NEO's CPU kernel parallelisation).

    Returns (attn_out [B,H,hd] replicated over model, new k_cache, new v_cache).
    """
    ctx = current_context()
    B, S, KV, hd = k_cache.shape
    H = q.shape[1]
    q_per_kv = H // KV
    scale = 1.0 / math.sqrt(hd)

    if ctx is None or "model" not in ctx.mesh.axis_names:
        # Single-device fallback: plain update + contiguous decode.
        kc = _write_at(k_cache, k_new, lens)
        vc = _write_at(v_cache, v_new, lens)
        out = decode_attention(q, kc, vc, lens + 1, window=window)
        return out, kc, vc

    mesh = ctx.mesh
    n_shards = mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes[0] if len(batch_axes) == 1 else batch_axes
    local_S = S // n_shards

    def kernel(q, kc, vc, kn, vn, lens):
        # shapes inside: q [Bl,H,hd], kc [Bl,local_S,KV,hd], lens [Bl]
        shard_idx = jax.lax.axis_index("model")
        offset = shard_idx * local_S
        # --- write the new token into the owning shard's chunk ---
        local_pos = lens - offset  # [Bl]
        owned = (local_pos >= 0) & (local_pos < local_S)
        safe_pos = jnp.clip(local_pos, 0, local_S - 1)
        bidx = jnp.arange(kc.shape[0])
        kc = kc.at[bidx, safe_pos].set(
            jnp.where(owned[:, None, None], kn, kc[bidx, safe_pos])
        )
        vc = vc.at[bidx, safe_pos].set(
            jnp.where(owned[:, None, None], vn, vc[bidx, safe_pos])
        )
        # --- partial attention over the local chunk ---
        new_lens = lens + 1
        pos = offset + jnp.arange(local_S)
        valid = pos[None, :] < new_lens[:, None]
        if window:
            valid &= pos[None, :] >= (new_lens[:, None] - window)
        kr = _repeat_kv(kc, q_per_kv)
        vr = _repeat_kv(vc, q_per_kv)
        acc, l, m = _partial_flash(q, kr, vr, valid, scale)
        # --- combine across shards (log-sum-exp weighted) ---
        m_glob = jax.lax.pmax(m, "model")  # [Bl, H]
        corr = jnp.exp(m - m_glob)
        num = jax.lax.psum(acc * corr[..., None], "model")
        den = jax.lax.psum(l * corr, "model")
        out = (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)
        return out, kc, vc

    mapped = jax.shard_map(
        kernel,
        mesh=mesh,
        check_vma=False,
        in_specs=(
            P(bspec, None, None),  # q replicated over model
            P(bspec, "model", None, None),
            P(bspec, "model", None, None),
            P(bspec, None, None),
            P(bspec, None, None),
            P(bspec),
        ),
        out_specs=(
            P(bspec, None, None),
            P(bspec, "model", None, None),
            P(bspec, "model", None, None),
        ),
    )
    return mapped(q, k_cache, v_cache, k_new, v_new, lens)


def _write_at(cache: jnp.ndarray, new: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
    """cache [B,S,KV,hd]; new [B,KV,hd]; write new at position lens[b]."""
    B, S = cache.shape[:2]
    bidx = jnp.arange(B)
    pos = jnp.clip(lens, 0, S - 1)
    return cache.at[bidx, pos].set(new.astype(cache.dtype))


def write_kv(cache_k, cache_v, k_new, v_new, lens):
    return _write_at(cache_k, k_new, lens), _write_at(cache_v, v_new, lens)
