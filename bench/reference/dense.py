"""Plain float32 reference of a dense decoder (Llama / Qwen3 layer), in
``jax.numpy``, for the benchmark's check of what the served path produced.

It imports nothing of the program.  The layer follows the published
description (Qwen3 / Llama ``modeling`` code): RMSNorm; q, k, v projections;
for Qwen3 an RMSNorm over each head's q and k (``qk_norm``); rotary position
embedding on two halves of the head (``rotate_half``); causal softmax
attention with grouped kv heads (query head h reads kv head h // (H / KV)),
scale 1/sqrt(head_dim); output projection; residual; RMSNorm; SwiGLU MLP
(silu(x W_gate) * x W_up) W_down; residual.  A final RMSNorm, then logits
against the output head (the embedding, transposed, when tied).

Every matmul runs at ``Precision.HIGHEST`` in float32, layer by layer, with
the weights made by ``bench.weights`` upcast one layer at a time, so a 9 GB
bf16 model never sits on the device in float32.

``quant="fp8"`` is the control: the same computation with every matmul
operand rounded to float8_e4m3fn with one scale per tensor (amax / 448), the
precision one step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 512


def _q(x: jax.Array, quant: Optional[str]) -> jax.Array:
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv  # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(w: Dict, x, dims: Dict, quant):
    """x: [T, d] float32, positions 0..T-1 (T padded; padding is causal-safe)."""
    T = x.shape[0]
    H, KV, hd, eps = dims["H"], dims["KV"], dims["hd"], dims["eps"]
    pos = jnp.arange(T)
    h = _rms(x, w["ln1"], eps)
    q = _mm("td,dhk->thk", h, w["wq"], quant)
    k = _mm("td,dhk->thk", h, w["wk"], quant)
    v = _mm("td,dhk->thk", h, w["wv"], quant)
    if dims["qk_norm"]:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q = _rope(q, pos, dims["theta"])
    k = _rope(k, pos, dims["theta"])
    g = H // KV
    qg = q.reshape(T, KV, g, hd)
    outs = []
    for c0 in range(0, T, Q_CHUNK):
        c1 = min(T, c0 + Q_CHUNK)
        s = _mm("tkgd,skd->kgts", qg[c0:c1], k, quant) / np.sqrt(hd)
        mask = pos[c0:c1, None] >= pos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("kgts,skd->tkgd", p, v, quant))
    o = jnp.concatenate(outs, axis=0).reshape(T, H, hd)
    x = x + _mm("thk,hkd->td", o, w["wo"], quant)
    h2 = _rms(x, w["ln2"], eps)
    gate = _mm("td,df->tf", h2, w["w_gate"], quant)
    up = _mm("td,df->tf", h2, w["w_up"], quant)
    return x + _mm("tf,fd->td", jax.nn.silu(gate) * up, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("dims_items", "quant"))
def _layer_step(blocks, l, x, *, dims_items, quant):
    dims = dict(dims_items)
    w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, l, keepdims=False).astype(jnp.float32), blocks)
    flat = dict(w["attn"], **w["mlp"], ln1=w["ln1"], ln2=w["ln2"])
    return _layer(flat, x, dims, quant)


@functools.partial(jax.jit, static_argnames=("dims_items", "quant"))
def _gaps(head, final_norm, x, rows, served, *, dims_items, quant):
    """Per scored row: the reference's best logit minus its logit of the
    served token; and, for the control, the reference's best minus its logit
    of the token the control puts first (``x`` then holds both streams)."""
    dims = dict(dims_items)
    h = _rms(x[rows], final_norm.astype(jnp.float32), dims["eps"])
    logits = jnp.einsum("td,dv->tv", h, head.astype(jnp.float32), precision=HI)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return best - got, logits


class Reference:
    """The reference over one model's weights (a ``bench.weights`` tree)."""

    def __init__(self, weights: Dict, dims: Dict):
        self.w = weights
        self.dims_items = tuple(sorted(
            (k, dims[k]) for k in ("H", "KV", "hd", "eps", "theta", "qk_norm")))
        self.L = dims["L"]
        self.head = weights["embed"].T if dims["tied"] else weights["unembed"]

    def hidden(self, tokens: np.ndarray, quant: Optional[str] = None) -> jax.Array:
        """Final-layer residual stream [T_pad, d] for ``tokens`` [T]."""
        T = len(tokens)
        T_pad = -(-T // Q_CHUNK) * Q_CHUNK
        ids = np.zeros((T_pad,), np.int32)
        ids[:T] = tokens
        x = jnp.take(self.w["embed"], jnp.asarray(ids), axis=0).astype(jnp.float32)
        x = _q(x, quant) if quant else x
        for l in range(self.L):
            x = _layer_step(self.w["blocks"]["sub0"], jnp.int32(l), x,
                            dims_items=self.dims_items, quant=quant)
        return x

    def served_gaps(self, prompt, served, control: bool = False
                    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Gap of each served token below the reference's best logit at its
        position; with ``control``, also the gap of the control's first
        choice at the same positions."""
        served = np.asarray(served, np.int32)
        seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
        rows = jnp.asarray(np.arange(len(prompt) - 1, len(seq)), jnp.int32)
        x = self.hidden(seq)
        gaps, logits = self._score(x, rows, served)
        ctrl = None
        if control:
            xc = self.hidden(seq, quant="fp8")
            _, c_logits = self._score(xc, rows, served, quant="fp8")
            first = jnp.argmax(c_logits, axis=-1)
            ctrl = np.asarray(jnp.max(logits, axis=-1)
                              - jnp.take_along_axis(logits, first[:, None], axis=-1)[:, 0])
        return np.asarray(gaps), ctrl

    def _score(self, x, rows, served, quant=None):
        head = self.head
        fn = self.w["final_norm"]
        if quant:
            x = _q(x, quant)
            head = _q(head.astype(jnp.float32), quant)
        return _gaps(head, fn, x, rows, jnp.asarray(served),
                     dims_items=self.dims_items, quant=quant)
