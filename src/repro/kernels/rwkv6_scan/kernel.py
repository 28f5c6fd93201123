"""Pallas TPU kernel: chunked RWKV6 (Finch) linear recurrence.

TPU adaptation of the chunked GLA/Finch algorithm: instead of a step-by-step
scan (1 token per VREG pass), each grid step processes a ``chunk`` of tokens
as MXU matmuls against the running [N, N] per-head state held in VMEM
scratch.  Intra-chunk pair decays are computed in log space with a small
[C, C, N] VMEM tensor (C=16, N padded to 128 lanes -> 128 KiB), which bounds
the exp() range to ``C * |log w|`` and keeps fp32 exact.

Grid: (B, H, n_chunks) — chunks innermost and sequential (state carry).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CLIP = 60.0


def _rwkv6_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,  # inputs
    y_ref, sT_ref,  # outputs
    s_scr,  # [N, N] f32 scratch (running state)
    *,
    chunk: int,
    n_chunks: int,
):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    rt = r_ref[0, :, 0, :].astype(jnp.float32)  # [C, N]
    kt = k_ref[0, :, 0, :].astype(jnp.float32)
    vt = v_ref[0, :, 0, :].astype(jnp.float32)
    wt = w_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # [N]
    S = s_scr[...]

    lw = jnp.log(jnp.maximum(wt, 1e-38))  # [C, N]
    b_incl = jnp.cumsum(lw, axis=0)
    b_excl = b_incl - lw

    # state term
    r_dec = rt * jnp.exp(b_excl)
    y_state = jax.lax.dot_general(
        r_dec, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [C, N]

    # intra-chunk term: scores[t, s] = sum_j r[t,j] k[s,j] exp(b_excl[t,j]-b_incl[s,j])
    pair = jnp.exp(
        jnp.clip(b_excl[:, None, :] - b_incl[None, :, :], -CLIP, CLIP)
    )  # [C, C, N]
    scores = jnp.sum(rt[:, None, :] * kt[None, :, :] * pair, axis=-1)  # [C, C]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(s_idx < t_idx, scores, 0.0)
    y_intra = jax.lax.dot_general(
        scores, vt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # current-token bonus
    su = jnp.sum(rt * u[None, :] * kt, axis=-1, keepdims=True)  # [C, 1]
    y = y_state + y_intra + su * vt
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state update
    total = b_incl[-1:, :]  # [1, N]
    k_dec = kt * jnp.exp(jnp.clip(total - b_incl, -CLIP, CLIP))
    s_new = jnp.exp(total.T) * S + jax.lax.dot_general(
        k_dec, vt, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [N(j), N(i)]
    s_scr[...] = s_new

    @pl.when(c == n_chunks - 1)
    def _write_state():
        sT_ref[0, 0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan_pallas(
    r: jnp.ndarray,  # [B, T, H, N]
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,  # [H, N]
    state0: jnp.ndarray,  # [B, H, N, N]
    *,
    chunk: int = 16,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, T, H, N = r.shape
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk

    # pad the channel dim to 128 lanes; w pads with 1.0 (log -> 0, no decay
    # overflow), everything else with 0 so padded channels stay inert.
    pad = (-N) % 128
    if pad:
        zpad = [(0, 0)] * 3 + [(0, pad)]
        r, k, v = (jnp.pad(a, zpad) for a in (r, k, v))
        w = jnp.pad(w, zpad, constant_values=1.0)
        u = jnp.pad(u, [(0, 0), (0, pad)])
        state0 = jnp.pad(state0, [(0, 0), (0, 0), (0, pad), (0, pad)])
    Np = N + pad

    grid = (B, H, n_chunks)
    y, sT = pl.pallas_call(
        functools.partial(_rwkv6_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, Np), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, Np), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, Np), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, Np), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, Np), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, Np, Np), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, Np), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, Np, Np), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, Np), r.dtype),
            jax.ShapeDtypeStruct((B, H, Np, Np), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Np, Np), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u, state0)
    if pad:
        y = y[..., :N]
        sT = sT[:, :, :N, :N]
    return y, sT
