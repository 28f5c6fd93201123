"""Jit'd wrapper for paged decode attention.

The implementation follows the platform: the compiled Pallas kernel on TPU,
the jnp reference elsewhere (the CPU).  The Pallas path pads head_dim to a
128 multiple and q-heads-per-kv to a sublane multiple of 8; the jnp
reference needs no padding.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_decode import ref as _ref


def platform_impl() -> str:
    """The decode-attention implementation for the default backend."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@partial(jax.jit, static_argnames=("impl", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, hd]
    k_pages: jnp.ndarray,  # [P, page, KV, hd]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, n_pages] int32
    lens: jnp.ndarray,  # [B] int32
    *,
    impl: Optional[str] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    impl = impl or platform_impl()
    if impl == "ref":
        return _ref.paged_decode_ref(q, k_pages, v_pages, block_tables, lens)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    from repro.kernels.paged_decode.kernel import paged_decode_pallas

    B, H, hd = q.shape
    KV = k_pages.shape[2]
    qpk = H // KV

    # pad head_dim to 128 lanes (q scaled to keep softmax temperature exact)
    hd_pad = (-hd) % 128
    if hd_pad:
        scale_fix = ((hd + hd_pad) ** 0.5) / (hd ** 0.5)
        q = jnp.pad(q, [(0, 0), (0, 0), (0, hd_pad)]) * scale_fix
        k_pages = jnp.pad(k_pages, [(0, 0), (0, 0), (0, 0), (0, hd_pad)])
        v_pages = jnp.pad(v_pages, [(0, 0), (0, 0), (0, 0), (0, hd_pad)])
    # pad q-heads-per-kv group to a multiple of 8 sublanes
    qpk_pad = (-qpk) % 8
    if qpk_pad:
        qr = q.reshape(B, KV, qpk, q.shape[-1])
        qr = jnp.pad(qr, [(0, 0), (0, 0), (0, qpk_pad), (0, 0)])
        q = qr.reshape(B, KV * (qpk + qpk_pad), q.shape[-1])

    out = paged_decode_pallas(q, k_pages, v_pages, block_tables, lens, interpret=interpret)

    if qpk_pad:
        out = out.reshape(B, KV, qpk + qpk_pad, -1)[:, :, :qpk].reshape(B, H, -1)
    if hd_pad:
        out = out[..., :hd]
    return out
