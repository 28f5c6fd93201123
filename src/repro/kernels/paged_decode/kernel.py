"""Pallas TPU kernel: paged GQA decode attention (split-K Flash-Decoding over
KV pages — the device-side counterpart of NEO's CPU paged-attention kernel).

Grid: (B, KV, n_pages) with the page dimension innermost and sequential.
The block table and sequence lengths are **scalar-prefetched** so each page's
DMA address is computed from ``block_tables[b, p]`` before the page arrives in
VMEM — the TPU analogue of the paper's block-granular CPU task partitioning.
Running (m, l, acc) flash state lives in VMEM scratch; pages past ``lens[b]``
are skipped with ``pl.when`` (no DMA wasted on dead pages beyond the table
padding entry 0).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    tables_ref,  # [B, n_pages] int32 (scalar prefetch)
    lens_ref,  # [B] int32 (scalar prefetch)
    q_ref,  # [1, 1, qpk, hd]
    k_ref,  # [1, 1, page, hd]
    v_ref,  # [1, 1, page, hd]
    o_ref,  # [1, 1, qpk, hd]
    m_scr,  # [qpk, 128] f32
    l_scr,  # [qpk, 128] f32
    acc_scr,  # [qpk, hd] f32
    *,
    scale: float,
    page: int,
    n_pages: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]

    @pl.when(p * page < seq_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [qpk, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [page, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [qpk, page]
        pos = p * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < seq_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(
            corr * l_scr[:, :1] + jnp.sum(pexp, axis=1, keepdims=True), l_scr.shape
        )
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(p == n_pages - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_pallas(
    q: jnp.ndarray,  # [B, H, hd]  (hd multiple of 128, qpk multiple of 8 — ops pads)
    k_pages: jnp.ndarray,  # [P, page, KV, hd]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, n_pages] int32
    lens: jnp.ndarray,  # [B] int32
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, hd = q.shape
    P, page, KV, _ = k_pages.shape
    n_pages = block_tables.shape[1]
    qpk = H // KV
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(B, KV, qpk, hd)
    # page-major layout per kv head: [KV, P, page, hd]
    kp = k_pages.transpose(2, 0, 1, 3)
    vp = v_pages.transpose(2, 0, 1, 3)

    grid = (B, KV, n_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qpk, hd), lambda b, h, p, t, l: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd), lambda b, h, p, t, l: (h, t[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page, hd), lambda b, h, p, t, l: (h, t[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qpk, hd), lambda b, h, p, t, l: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qpk, 128), jnp.float32),
            pltpu.VMEM((qpk, 128), jnp.float32),
            pltpu.VMEM((qpk, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page=page, n_pages=n_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, qpk, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables, lens, qr, kp, vp)
    return out.reshape(B, H, hd)
