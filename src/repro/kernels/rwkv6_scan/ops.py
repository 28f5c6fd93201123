"""Jit'd dispatch wrapper for the RWKV6 recurrence.

impl:
  "scan"    — naive lax.scan oracle (default on CPU; tiny HLO, scan-friendly)
  "chunked" — exact chunked-parallel jnp form
  "pallas"  — Pallas TPU kernel (interpret=True on CPU for validation)
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6_scan import ref as _ref


@partial(jax.jit, static_argnames=("impl", "chunk", "interpret"))
def rwkv6_scan(
    r: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    state0: jnp.ndarray,
    *,
    impl: str = "scan",
    chunk: int = 16,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if impl == "scan":
        return _ref.rwkv6_scan_ref(r, k, v, w, u, state0)
    if impl == "chunked":
        return _ref.rwkv6_chunked_ref(r, k, v, w, u, state0, chunk=chunk)
    if impl == "pallas":
        from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_pallas

        return rwkv6_scan_pallas(r, k, v, w, u, state0, chunk=chunk, interpret=interpret)
    raise ValueError(f"unknown impl {impl!r}")


def rwkv6_decode_step(
    r: jnp.ndarray,  # [B, H, N]
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,  # [H, N]
    state: jnp.ndarray,  # [B, H, N, N]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token decode: O(1) in sequence length."""
    r32, k32, v32, w32 = (a.astype(jnp.float32) for a in (r, k, v, w))
    kv = k32[..., :, None] * v32[..., None, :]
    u32 = u.astype(jnp.float32)[None, :, :, None]
    y = jnp.einsum("bhj,bhji->bhi", r32, state + u32 * kv)
    state = w32[..., :, None] * state + kv
    return y.astype(r.dtype), state
