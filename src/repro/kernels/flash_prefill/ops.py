"""Jit'd wrapper for flash prefill attention: pads head_dim to an MXU-aligned
multiple of 128 and dispatches to the Pallas kernel or the jnp oracle."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_prefill import ref as _ref


def _pad_hd(x: jnp.ndarray, mult: int = 128):
    hd = x.shape[-1]
    pad = (-hd) % mult
    if pad == 0:
        return x, hd
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]), hd


@partial(jax.jit, static_argnames=("causal", "window", "impl", "blk_q", "blk_k", "interpret"))
def flash_prefill(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    impl: str = "ref",
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    if impl == "ref":
        return _ref.flash_prefill_ref(q, k, v, causal=causal, window=window)
    if impl == "pallas":
        from repro.kernels.flash_prefill.kernel import flash_prefill_pallas

        qp, hd = _pad_hd(q)
        kp, _ = _pad_hd(k)
        vp, _ = _pad_hd(v)
        # NOTE: softmax scale must use the true head_dim, not the padded one —
        # the kernel receives padded tensors, so rescale q to compensate.
        if qp.shape[-1] != hd:
            qp = qp * (qp.shape[-1] ** 0.5) / (hd ** 0.5)
        out = flash_prefill_pallas(
            qp, kp, vp, causal=causal, window=window,
            blk_q=blk_q, blk_k=blk_k, interpret=interpret,
        )
        return out[..., :hd]
    raise ValueError(f"unknown impl {impl!r}")
