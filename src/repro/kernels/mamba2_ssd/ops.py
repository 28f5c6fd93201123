"""Jit'd dispatch wrapper for the Mamba2 SSD recurrence."""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.mamba2_ssd import ref as _ref


@partial(jax.jit, static_argnames=("impl", "chunk", "interpret"))
def mamba2_ssd(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    Bm: jnp.ndarray,
    Cm: jnp.ndarray,
    D: jnp.ndarray,
    state0: jnp.ndarray,
    *,
    impl: str = "scan",
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if impl == "scan":
        return _ref.mamba2_ssd_ref(x, dt, A, Bm, Cm, D, state0)
    if impl == "chunked":
        return _ref.mamba2_ssd_chunked_ref(x, dt, A, Bm, Cm, D, state0, chunk=chunk)
    if impl == "pallas":
        from repro.kernels.mamba2_ssd.kernel import mamba2_ssd_pallas

        return mamba2_ssd_pallas(x, dt, A, Bm, Cm, D, state0, chunk=chunk, interpret=interpret)
    raise ValueError(f"unknown impl {impl!r}")


def mamba2_decode_step(
    x: jnp.ndarray,  # [B, H, P]
    dt: jnp.ndarray,  # [B, H]
    A: jnp.ndarray,  # [H]
    Bm: jnp.ndarray,  # [B, N]
    Cm: jnp.ndarray,  # [B, N]
    D: jnp.ndarray,  # [H]
    state: jnp.ndarray,  # [B, H, N, P]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x32, dt32, B32, C32 = (a.astype(jnp.float32) for a in (x, dt, Bm, Cm))
    a = jnp.exp(dt32 * A.astype(jnp.float32)[None])
    upd = (dt32[..., None] * x32)[:, :, None, :] * B32[:, None, :, None]
    state = a[..., None, None] * state + upd
    y = jnp.einsum("bn,bhnp->bhp", C32, state) + D.astype(jnp.float32)[None, :, None] * x32
    return y.astype(x.dtype), state
