"""Pallas TPU kernel: chunked Mamba2 SSD recurrence (arXiv:2405.21060).

Scalar per-head decay makes the chunked form pure MXU work: per chunk the
kernel does three [C, N] x [N, P]-class matmuls against the [N, P] running
state in VMEM scratch, with a [C, C] pair-decay matrix (scalar decay ⇒ 2-D,
unlike RWKV6's per-channel [C, C, N]).

The wrapper pre-computes ``xdt = x * dt`` and ``la = dt * A`` (lane-broadcast)
and adds the ``D * x`` skip term outside the kernel.

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


def _ssd_kernel(
    xdt_ref,  # [1, C, 1, P]
    la_ref,  # [1, C, 1, 128] (lane-broadcast log-decay)
    b_ref,  # [1, C, N]
    c_ref,  # [1, C, N]
    s0_ref,  # [1, 1, N, P]
    y_ref,  # [1, C, 1, P]
    sT_ref,  # [1, 1, N, P]
    s_scr,  # [N, P] f32
    *,
    chunk: int,
    n_chunks: int,
):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    xdt = xdt_ref[0, :, 0, :].astype(jnp.float32)  # [C, P]
    la = la_ref[0, :, 0, :1].astype(jnp.float32)  # [C, 1]
    bt = b_ref[0].astype(jnp.float32)  # [C, N]
    ct = c_ref[0].astype(jnp.float32)  # [C, N]
    S = s_scr[...]

    cum = jnp.cumsum(la, axis=0)  # [C, 1] inclusive
    dec_t = jnp.exp(cum)
    y_state = jax.lax.dot_general(
        ct * dec_t, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [C, P]

    pair = jnp.exp(jnp.clip(cum - cum.T, -CLIP, CLIP))  # [C, C]
    cb = jax.lax.dot_general(
        ct, bt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [C, C]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(s_idx <= t_idx, cb * pair, 0.0)
    y_intra = jax.lax.dot_general(
        scores, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[0, :, 0, :] = (y_state + y_intra).astype(y_ref.dtype)

    total = cum[-1:, :]  # [1, 1]
    k_dec = bt * jnp.exp(jnp.clip(total - cum, -CLIP, CLIP))  # [C, N]
    s_new = jnp.exp(total) * S + jax.lax.dot_general(
        k_dec, xdt, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [N, P]
    s_scr[...] = s_new

    @pl.when(c == n_chunks - 1)
    def _write_state():
        sT_ref[0, 0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_ssd_pallas(
    x: jnp.ndarray,  # [B, T, H, P]
    dt: jnp.ndarray,  # [B, T, H]
    A: jnp.ndarray,  # [H]
    Bm: jnp.ndarray,  # [B, T, N]
    Cm: jnp.ndarray,  # [B, T, N]
    D: jnp.ndarray,  # [H]
    state0: jnp.ndarray,  # [B, H, N, P]
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk

    x32 = x.astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    xdt = x32 * dt32[..., None]  # [B, T, H, P]
    la = (dt32 * A.astype(jnp.float32)[None, None])[..., None]  # [B, T, H, 1]
    la = jnp.broadcast_to(la, (B, T, H, 128))

    padP = (-P) % 128
    padN = (-N) % 128
    if padP:
        xdt = jnp.pad(xdt, [(0, 0), (0, 0), (0, 0), (0, padP)])
    if padN:
        Bm = jnp.pad(Bm, [(0, 0), (0, 0), (0, padN)])
        Cm = jnp.pad(Cm, [(0, 0), (0, 0), (0, padN)])
    if padP or padN:
        state0 = jnp.pad(state0, [(0, 0), (0, 0), (0, padN), (0, padP)])
    Pp, Np = P + padP, N + padN

    grid = (B, H, n_chunks)
    y, sT = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, Pp), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, 128), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, Np), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, Np), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, Np, Pp), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, Pp), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, Np, Pp), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, Pp), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Np, Pp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Np, Pp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xdt.astype(jnp.float32), la, Bm.astype(jnp.float32), Cm.astype(jnp.float32), state0.astype(jnp.float32))

    if padP or padN:
        y = y[..., :P]
        sT = sT[:, :, :N, :P]
    y = y + D.astype(jnp.float32)[None, None, :, None] * x32
    return y.astype(x.dtype), sT
