"""HostAttention (the paper's PACPU CPU kernel, numpy flavour) vs the jnp
paged-attention oracle, including the flash-decoding split and threading."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.host_attention import HostAttention
from repro.kernels.paged_decode.ops import paged_decode_attention


def make_pool(rng, L, P, page, KV, hd):
    k = rng.normal(size=(L, P, page, KV, hd)).astype(np.float32)
    v = rng.normal(size=(L, P, page, KV, hd)).astype(np.float32)
    return k, v


@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("split_pages", [1, 2, 32])
def test_host_attention_matches_oracle(threads, split_pages, rng):
    cfg = get_smoke_config("qwen3-0.6b")
    L, P, page = 2, 16, cfg.kv_block_size
    KV, hd, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, L, P, page, KV, hd)
    ha = HostAttention(cfg, pk, pv, threads=threads, split_pages=split_pages)
    R = 5
    tables = rng.integers(0, P, size=(R, 4)).astype(np.int32)
    lens = rng.integers(1, 4 * page, size=(R,)).astype(np.int32)
    q = rng.normal(size=(R, H, hd)).astype(np.float32)
    for layer in range(L):
        out = ha.attend(layer, q, tables, lens)
        oracle = paged_decode_attention(
            jnp.asarray(q), jnp.asarray(pk[layer]), jnp.asarray(pv[layer]),
            jnp.asarray(tables), jnp.asarray(lens), impl="ref")
        np.testing.assert_allclose(out, np.asarray(oracle), rtol=1e-4, atol=1e-4)


def test_host_attention_append_then_attend(rng):
    """run_layer writes the new token then attends over len+1."""
    cfg = get_smoke_config("qwen3-0.6b")
    L, P, page = 1, 8, cfg.kv_block_size
    KV, hd, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, L, P, page, KV, hd)
    ha = HostAttention(cfg, pk, pv)
    D = 4
    q = rng.normal(size=(D, H, hd)).astype(np.float32)
    k_new = rng.normal(size=(D, KV, hd)).astype(np.float32)
    v_new = rng.normal(size=(D, KV, hd)).astype(np.float32)
    host_rows = np.asarray([1, 3])
    tables = np.asarray([[0, 1], [2, 3]], np.int32)
    lens = np.asarray([page - 1, page + 3], np.int32)  # one crosses a boundary
    page_ids = np.asarray([0, 3], np.int32)
    offsets = np.asarray([page - 1, 3 + 1 - 1], np.int32)
    offsets = (lens % page).astype(np.int32)
    page_ids = np.asarray([tables[i][lens[i] // page] for i in range(2)], np.int32)
    out = ha.run_layer(0, q, k_new, v_new, host_rows=host_rows, tables=tables,
                       lens=lens, page_ids=page_ids, offsets=offsets)
    # rows not in host_rows stay zero
    assert np.all(out[0] == 0) and np.all(out[2] == 0)
    # pool now contains the appended tokens at the right slots
    for i, r in enumerate(host_rows):
        pid, off = page_ids[i], offsets[i]
        np.testing.assert_array_equal(pk[0, pid, off], k_new[r])
    # oracle over the UPDATED pool with len+1
    oracle = paged_decode_attention(
        jnp.asarray(q[host_rows]), jnp.asarray(pk[0]), jnp.asarray(pv[0]),
        jnp.asarray(tables), jnp.asarray(lens + 1), impl="ref")
    np.testing.assert_allclose(out[host_rows], np.asarray(oracle), rtol=1e-4, atol=1e-4)
    assert ha.busy_time > 0 and ha.bytes_read > 0


def test_host_attention_window(rng):
    cfg = get_smoke_config("zamba2-7b")
    L, P, page = 1, 8, cfg.kv_block_size
    KV, hd, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, L, P, page, KV, hd)
    ha = HostAttention(cfg, pk, pv)
    q = rng.normal(size=(1, H, hd)).astype(np.float32)
    tables = np.asarray([[0, 1, 2, 3]], np.int32)
    n_tokens = np.asarray([4 * page], np.int32)
    win = 2 * page
    out = ha.attend(0, q, tables, n_tokens, window=win)
    # oracle: zero-out masked tokens by building a truncated pool view
    k_lin = pk[0, tables[0]].reshape(-1, KV, hd)[-win:]
    v_lin = pv[0, tables[0]].reshape(-1, KV, hd)[-win:]
    qpk = H // KV
    s = np.einsum("kqd,tkd->kqt", q[0].reshape(KV, qpk, hd), k_lin) / np.sqrt(hd)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("kqt,tkd->kqd", p, v_lin).reshape(H, hd)
    np.testing.assert_allclose(out[0], o, rtol=1e-4, atol=1e-4)


def test_prefix_partials_merge_matches_prefix_attention(rng):
    """Zero-copy host serving oracle: host-computed prefix flash partials
    merged with the device's causal-suffix attention must equal the joint
    softmax over [prefix, causal suffix] (attn_lib.prefix_attention)."""
    from repro.models import attention as attn_lib

    cfg = get_smoke_config("qwen3-0.6b")
    L, P, page = 2, 16, cfg.kv_block_size
    KV, hd, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, L, P, page, KV, hd)
    ha = HostAttention(cfg, pk, pv)
    B, S = 3, 7
    tables = rng.integers(0, P, size=(B, 3)).astype(np.int32)
    # row 2 has NO prefix: the merge must reduce to pure causal attention
    prefix_lens = np.array([3 * page - 5, page + 2, 0], np.int32)
    q = rng.normal(size=(B, S, H, hd)).astype(np.float32)
    k_new = rng.normal(size=(B, S, KV, hd)).astype(np.float32)
    v_new = rng.normal(size=(B, S, KV, hd)).astype(np.float32)
    for layer in range(L):
        acc, l, m = ha.prefix_partials(layer, q, tables, prefix_lens)
        merged = attn_lib.suffix_attention_merge(
            jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(acc), jnp.asarray(l), jnp.asarray(m))
        # oracle: gather the prefix KV densely and run the joint softmax
        T = 3 * page
        pre_k = np.zeros((B, T, KV, hd), np.float32)
        pre_v = np.zeros((B, T, KV, hd), np.float32)
        for b in range(B):
            n = int(prefix_lens[b])
            if n:
                pre_k[b, :n] = pk[layer, tables[b]].reshape(-1, KV, hd)[:n]
                pre_v[b, :n] = pv[layer, tables[b]].reshape(-1, KV, hd)[:n]
        oracle = attn_lib.prefix_attention(
            jnp.asarray(q), jnp.asarray(pre_k), jnp.asarray(pre_v),
            jnp.asarray(prefix_lens), jnp.asarray(k_new), jnp.asarray(v_new))
        np.testing.assert_allclose(np.asarray(merged), np.asarray(oracle),
                                   rtol=1e-4, atol=1e-4)
    assert ha.prefix_bytes_read > 0  # in-place gather was accounted
    assert ha.busy_time == 0.0  # and kept OUT of the decode-attn EWMA signal

