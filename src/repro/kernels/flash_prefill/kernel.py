"""Pallas TPU kernel: causal GQA flash attention for the prefill stage.

TPU adaptation of FlashAttention-2 (arXiv:2307.08691): the [S, S] score
matrix never leaves VMEM; tiles are MXU-aligned (q/k blocks of 128 rows x
head_dim lanes, head_dim padded to a 128 multiple by the ops wrapper).

Grid: (B, H, n_q_blocks, n_kv_blocks), kv innermost and sequential —
running (m, l, acc) state lives in VMEM scratch and is carried across the kv
dimension of the grid; causally-dead kv blocks are skipped via ``pl.when``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, 1, blk_q, hd]
    k_ref,  # [1, 1, blk_k, hd]
    v_ref,  # [1, 1, blk_k, hd]
    o_ref,  # [1, 1, blk_q, hd]
    m_scr,  # [blk_q, 128] f32
    l_scr,  # [blk_q, 128] f32
    acc_scr,  # [blk_q, hd] f32
    *,
    scale: float,
    blk_q: int,
    blk_k: int,
    n_kv: int,
    causal: bool,
    window: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = kj * blk_k

    # A kv block is live unless it is entirely above the causal diagonal or
    # entirely outside the sliding window.
    live = True
    if causal:
        live = k_start <= q_start + blk_q - 1
    if window:
        live = jnp.logical_and(live, q_start - (k_start + blk_k - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [blk_q, blk_k]
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = jnp.ones((blk_q, blk_k), bool)
        if causal:
            mask &= rows >= cols
        if window:
            mask &= rows - cols < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # [blk_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kj == n_kv - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "blk_q", "blk_k", "interpret"),
)
def flash_prefill_pallas(
    q: jnp.ndarray,  # [B, S, H, hd] (hd a multiple of 128; ops wrapper pads)
    k: jnp.ndarray,  # [B, S, KV, hd]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, hd = q.shape
    KV = k.shape[2]
    qpk = H // KV
    scale = 1.0 / math.sqrt(hd)
    blk_q = min(blk_q, S)
    blk_k = min(blk_k, S)
    assert S % blk_q == 0 and S % blk_k == 0, (S, blk_q, blk_k)
    n_q, n_kv = S // blk_q, S // blk_k

    # head-major layouts for clean tiling
    qt = q.swapaxes(1, 2)  # [B, H, S, hd]
    kt = k.swapaxes(1, 2)  # [B, KV, S, hd]
    vt = v.swapaxes(1, 2)

    grid = (B, H, n_q, n_kv)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            blk_q=blk_q,
            blk_k=blk_k,
            n_kv=n_kv,
            causal=causal,
            window=window,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, i, j: (b, h // qpk, j, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, i, j: (b, h // qpk, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.swapaxes(1, 2)  # [B, S, H, hd]
