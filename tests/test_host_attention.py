"""HostAttention (the paper's PACPU CPU kernel: the native C kernel and its
numpy fallback) vs the jnp paged-attention oracle, including the
flash-decoding split and threading."""

import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import host_attention
from repro.core.host_attention import HostAttention, widen
from repro.kernels.paged_decode.ops import paged_decode_attention


def make_pool(rng, L, P, page, KV, hd, dtype=np.float32):
    k = rng.normal(size=(L, P, page, KV, hd)).astype(np.float32).astype(dtype)
    v = rng.normal(size=(L, P, page, KV, hd)).astype(np.float32).astype(dtype)
    return k, v


@pytest.fixture(params=["native", "numpy"])
def kernel(request, monkeypatch):
    """The task kernel every HostAttention of the test runs: the C kernel
    (where a compiler is found) or the numpy fallback."""
    if request.param == "native":
        if shutil.which(host_attention._CC[0]) is None:
            pytest.skip("no C compiler")
    else:
        monkeypatch.setattr(host_attention, "native_library", lambda: None)
    return request.param


def make_attention(kernel, *args, **kw) -> HostAttention:
    ha = HostAttention(*args, **kw)
    assert ha.kernel == kernel
    return ha


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("split_pages", [1, 2, 32])
def test_host_attention_matches_oracle(threads, split_pages, dtype, kernel, rng):
    """A bf16 pool is compared against the oracle on its widened values."""
    cfg = get_smoke_config("qwen3-0.6b")
    L, P, page = 2, 16, cfg.kv_block_size
    KV, hd, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, L, P, page, KV, hd, dtype)
    ha = make_attention(kernel, cfg, pk, pv, threads=threads,
                        split_pages=split_pages)
    R = 5
    tables = rng.integers(0, P, size=(R, 4)).astype(np.int32)
    lens = rng.integers(1, 4 * page, size=(R,)).astype(np.int32)
    q = rng.normal(size=(R, H, hd)).astype(np.float32)
    pk32, pv32 = pk.astype(np.float32), pv.astype(np.float32)
    for layer in range(L):
        out = ha.attend(layer, q, tables, lens)
        oracle = paged_decode_attention(
            jnp.asarray(q), jnp.asarray(pk32[layer]), jnp.asarray(pv32[layer]),
            jnp.asarray(tables), jnp.asarray(lens), impl="ref")
        np.testing.assert_allclose(out, np.asarray(oracle), rtol=1e-4, atol=1e-4)


def test_widen_is_exact_on_bf16_bits(rng):
    """The integer widen gives the float32 of every bf16 value, specials
    included."""
    x = np.concatenate([rng.normal(size=1000).astype(np.float32) * 1e3,
                        np.asarray([0.0, -0.0, np.inf, -np.inf, 1e-40],
                                   np.float32)]).astype(jnp.bfloat16)
    out = widen(x.view(np.uint16), np.empty(x.shape, np.float32))
    np.testing.assert_array_equal(out, x.astype(np.float32))
    assert np.isnan(widen(np.asarray([np.nan], jnp.bfloat16).view(np.uint16),
                          np.empty(1, np.float32))[0])


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("kv_heads, heads", [(4, 32), (1, 8)])
def test_host_attention_group8_matches_oracle(kv_heads, heads, dtype, kernel,
                                              rng):
    """A GQA group of 8 (Yi's shape: KV 4 / H 32, and KV 1 / H 8) at the
    smoke head_dim, rows over several blocks and a masked window start."""
    cfg = get_smoke_config("qwen3-0.6b")
    P, page, hd = 32, cfg.kv_block_size, cfg.head_dim
    pk, pv = make_pool(rng, 1, P, page, kv_heads, hd, dtype)
    ha = make_attention(kernel, cfg, pk, pv, threads=4, split_pages=3)
    R, MP = 4, 8
    tables = np.stack([rng.permutation(P)[:MP] for _ in range(R)]).astype(np.int32)
    lens = np.asarray([1, 5 * page + 3, MP * page, 2 * page], np.int32)
    q = rng.normal(size=(R, heads, hd)).astype(np.float32)
    out = ha.attend(0, q, tables, lens)
    oracle = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk[0].astype(np.float32)),
        jnp.asarray(pv[0].astype(np.float32)), jnp.asarray(tables),
        jnp.asarray(lens), impl="ref")
    np.testing.assert_allclose(out, np.asarray(oracle), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_host_attention_row_bitwise_independent(dtype, kernel, rng):
    """A row's output is bitwise the same for 1 or 8 threads and alone or
    inside a 6-row call: each row merges its own blocks in a fixed order."""
    cfg = get_smoke_config("qwen3-0.6b")
    L, P, page = 1, 64, cfg.kv_block_size
    KV, hd, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, L, P, page, KV, hd, dtype)
    R, MP = 6, 12
    tables = np.stack([rng.permutation(P)[:MP] for _ in range(R)]).astype(np.int32)
    lens = rng.integers(1, MP * page, size=(R,)).astype(np.int32)
    lens[3] = MP * page - 3  # the row under test spans several blocks
    q = rng.normal(size=(R, H, hd)).astype(np.float32)
    outs = []
    for threads in (1, 8):
        ha = make_attention(kernel, cfg, pk, pv, threads=threads, split_pages=3)
        outs.append(ha.attend(0, q, tables, lens)[3])
        outs.append(ha.attend(0, q[3:4], tables[3:4], lens[3:4])[0])
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


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


def test_concurrent_callers_share_workers_safely(kernel):
    """Host lanes call one HostAttention at once: more caller threads than
    cores, with a short switch interval, must each get the serial result
    bit for bit (every thread owns its gather/widen scratch; every native
    call its own task counter and partials)."""
    import sys
    import threading

    rng = np.random.default_rng(5)
    cfg = get_smoke_config("qwen3-0.6b")
    page, KV, hd, H = cfg.kv_block_size, cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, 1, 48, page, KV, hd, jnp.bfloat16)
    R, MP = 3, 8
    tables = np.stack([rng.permutation(48)[:MP] for _ in range(R)]).astype(np.int32)
    lens = rng.integers(1, MP * page, size=(R,)).astype(np.int32)
    callers = 2 * (os.cpu_count() or 4)
    qs = rng.normal(size=(callers, R, H, hd)).astype(np.float32)
    want = [make_attention(kernel, cfg, pk, pv, split_pages=2).attend(
        0, q, tables, lens) for q in qs]
    ha = make_attention(kernel, cfg, pk, pv, threads=4, split_pages=2)
    got = [None] * callers

    def call(c):
        for _ in range(5):
            got[c] = ha.attend(0, qs[c], tables, lens)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=call, args=(c,)) for c in range(callers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    for c in range(callers):
        np.testing.assert_array_equal(got[c], want[c])


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_kv_head_shards_concat_to_whole(dtype, kernel, rng):
    """TP host shards attend over kv-head slice views of one pool; their
    outputs, concatenated along heads, equal the whole pool's bit for bit."""
    cfg = get_smoke_config("qwen3-0.6b")
    page, KV, hd, H = cfg.kv_block_size, cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, 1, 16, page, KV, hd, dtype)
    R = 3
    tables = np.stack([rng.permutation(16)[:4] for _ in range(R)]).astype(np.int32)
    lens = rng.integers(1, 4 * page, size=(R,)).astype(np.int32)
    q = rng.normal(size=(R, H, hd)).astype(np.float32)
    whole = make_attention(kernel, cfg, pk, pv, split_pages=2).attend(
        0, q, tables, lens)
    hs, ks = H // KV, 1
    parts = [make_attention(kernel, cfg, pk[:, :, :, s:s + ks],
                            pv[:, :, :, s:s + ks], split_pages=2).attend(
                                0, q[:, s * hs:(s + ks) * hs], tables, lens)
             for s in range(KV)]
    np.testing.assert_array_equal(np.concatenate(parts, axis=1), whole)


def test_append_stores_device_bf16_bits(rng):
    """A bf16 pool stores the appended token as the device pool does: the
    float32 value rounded to bf16 by the same cast."""
    cfg = get_smoke_config("qwen3-0.6b")
    page, KV, hd, H = cfg.kv_block_size, cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, 1, 4, page, KV, hd, jnp.bfloat16)
    ha = HostAttention(cfg, pk, pv)
    k_new = rng.normal(size=(2, KV, hd)).astype(np.float32)
    v_new = rng.normal(size=(2, KV, hd)).astype(np.float32)
    ha.run_layer(0, rng.normal(size=(2, H, hd)).astype(np.float32), k_new,
                 v_new, host_rows=np.asarray([1]), tables=np.asarray([[2]]),
                 lens=np.asarray([4]), page_ids=np.asarray([2]),
                 offsets=np.asarray([4]))
    dev_k = np.asarray(jnp.asarray(k_new[1]).astype(jnp.bfloat16))
    dev_v = np.asarray(jnp.asarray(v_new[1]).astype(jnp.bfloat16))
    np.testing.assert_array_equal(pk[0, 2, 4].view(np.uint16), dev_k.view(np.uint16))
    np.testing.assert_array_equal(pv[0, 2, 4].view(np.uint16), dev_v.view(np.uint16))


@pytest.mark.parametrize("win_pages", [2, 1.5])
def test_host_attention_window(win_pages, kernel, rng):
    """A sliding window of whole pages, and one that starts inside a page
    (its first block masks the tokens before the window)."""
    cfg = get_smoke_config("zamba2-7b")
    L, P, page = 1, 8, cfg.kv_block_size
    KV, hd, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, L, P, page, KV, hd)
    ha = make_attention(kernel, cfg, pk, pv, threads=2, split_pages=1)
    q = rng.normal(size=(1, H, hd)).astype(np.float32)
    tables = np.asarray([[0, 1, 2, 3]], np.int32)
    n_tokens = np.asarray([4 * page], np.int32)
    win = int(win_pages * page)
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


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_prefix_partials_merge_matches_prefix_attention(dtype, rng):
    """Zero-copy host serving oracle: host-computed prefix flash partials
    merged with the device's causal-suffix attention must equal the joint
    softmax over [prefix, causal suffix] (attn_lib.prefix_attention), on
    the pool's widened values."""
    from repro.models import attention as attn_lib

    cfg = get_smoke_config("qwen3-0.6b")
    L, P, page = 2, 16, cfg.kv_block_size
    KV, hd, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, L, P, page, KV, hd, dtype)
    ha = HostAttention(cfg, pk, pv)
    pk, pv = pk.astype(np.float32), pv.astype(np.float32)
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
    # the in-place gather was accounted at the pool's byte width
    assert ha.prefix_bytes_read == (L * 2 * int(prefix_lens.sum()) * KV * hd
                                    * np.dtype(dtype).itemsize)
    assert ha.busy_time == 0.0  # and kept OUT of the decode-attn EWMA signal



def test_native_kernel_engages_and_counts_calls(rng):
    """Where a C compiler is found, HostAttention builds and runs the native
    kernel, and counts the calls it serves (not the calls with no host
    row)."""
    if shutil.which(host_attention._CC[0]) is None:
        pytest.skip("no C compiler")
    cfg = get_smoke_config("qwen3-0.6b")
    page, KV, hd, H = cfg.kv_block_size, cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, 1, 8, page, KV, hd, jnp.bfloat16)
    ha = HostAttention(cfg, pk, pv, threads=2)
    assert ha.kernel == "native" and ha.native_calls == 0
    q = rng.normal(size=(2, H, hd)).astype(np.float32)
    tables = np.asarray([[0, 1], [2, 3]], np.int32)
    lens = np.asarray([page + 3, 5], np.int32)
    ha.attend(0, q, tables, lens)
    ha.run_layer(0, q, q[:, :KV], q[:, :KV], host_rows=np.asarray([0]),
                 tables=tables[:1], lens=lens[:1], page_ids=np.asarray([1]),
                 offsets=np.asarray([3]))
    ha.run_layer(0, q, q[:, :KV], q[:, :KV], host_rows=np.asarray([], int),
                 tables=tables[:0], lens=lens[:0], page_ids=lens[:0],
                 offsets=lens[:0])
    assert ha.native_calls == 2


@pytest.mark.parametrize("cc", [("false",), ("no-such-compiler", "-O3")])
def test_failed_build_falls_back_to_numpy(cc, monkeypatch, rng, caplog):
    """A compiler that fails, or none at all, leaves the numpy kernel, which
    still matches the oracle."""
    monkeypatch.setattr(host_attention, "_CC", cc)
    cfg = get_smoke_config("qwen3-0.6b")
    page, KV, hd, H = cfg.kv_block_size, cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    pk, pv = make_pool(rng, 1, 8, page, KV, hd, jnp.bfloat16)
    with caplog.at_level("WARNING", logger=host_attention.__name__):
        ha = HostAttention(cfg, pk, pv, threads=2)
    assert ha.kernel == "numpy"
    assert "no native kernel" in caplog.text
    q = rng.normal(size=(2, H, hd)).astype(np.float32)
    tables = np.asarray([[0, 1], [2, 3]], np.int32)
    lens = np.asarray([page + 3, 5], np.int32)
    out = ha.attend(0, q, tables, lens)
    assert ha.native_calls == 0
    oracle = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk[0].astype(np.float32)),
        jnp.asarray(pv[0].astype(np.float32)), jnp.asarray(tables),
        jnp.asarray(lens), impl="ref")
    np.testing.assert_allclose(out, np.asarray(oracle), rtol=1e-4, atol=1e-4)


def test_head_dim_off_the_vector_width_runs_numpy(rng):
    """A head_dim that is not a multiple of the native kernel's 16 lanes
    takes the numpy kernel, with no error."""
    cfg = get_smoke_config("qwen3-0.6b")
    page, KV, H, hd = cfg.kv_block_size, 2, 4, 24
    pk, pv = make_pool(rng, 1, 8, page, KV, hd, jnp.bfloat16)
    ha = HostAttention(cfg, pk, pv, threads=2)
    assert ha.kernel == "numpy"
    q = rng.normal(size=(2, H, hd)).astype(np.float32)
    tables = np.asarray([[0, 1], [2, 3]], np.int32)
    lens = np.asarray([page + 3, 5], np.int32)
    oracle = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk[0].astype(np.float32)),
        jnp.asarray(pv[0].astype(np.float32)), jnp.asarray(tables),
        jnp.asarray(lens), impl="ref")
    np.testing.assert_allclose(ha.attend(0, q, tables, lens),
                               np.asarray(oracle), rtol=1e-4, atol=1e-4)
