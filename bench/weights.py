"""Weights of a run, made by the benchmark from ``--seed`` on the device.

One jitted call makes every leaf, in the dtype it is served in, in the tree
layout ``NeoEngine`` takes (``params=``): stacked ``[L, ...]`` leaves under
``blocks/sub0``.  The plain reference calls the same function again after
the program's state is freed, so both see the same values and the
reference takes nothing that the program made.

Matrices are normal with std 1/sqrt(fan_in) (logits of order 1 at any
width); norm scales are 1 + 0.1 N(0, 1), so that a norm applied wrongly
shows in the comparison.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any whole ``seed`` (jax.random.key keeps 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def leaf_specs(dims: Dict) -> Dict[str, Tuple[tuple, str, float]]:
    """``path -> (shape, dtype, std)``; std 0 marks a norm scale."""
    L, d, H, KV, hd, f, V = (dims[k] for k in ("L", "d", "H", "KV", "hd", "f", "V"))
    dt = dims["dtype"]
    s = {
        "embed": ((V, d), dt, d ** -0.5),
        "final_norm": ((d,), "float32", 0.0),
        "blocks/sub0/ln1": ((L, d), "float32", 0.0),
        "blocks/sub0/ln2": ((L, d), "float32", 0.0),
        "blocks/sub0/attn/wq": ((L, d, H, hd), dt, d ** -0.5),
        "blocks/sub0/attn/wk": ((L, d, KV, hd), dt, d ** -0.5),
        "blocks/sub0/attn/wv": ((L, d, KV, hd), dt, d ** -0.5),
        "blocks/sub0/attn/wo": ((L, H, hd, d), dt, (H * hd) ** -0.5),
        "blocks/sub0/mlp/w_gate": ((L, d, f), dt, d ** -0.5),
        "blocks/sub0/mlp/w_up": ((L, d, f), dt, d ** -0.5),
        "blocks/sub0/mlp/w_down": ((L, f, d), dt, f ** -0.5),
    }
    if not dims["tied"]:
        s["unembed"] = ((d, V), dt, d ** -0.5)
    if dims["qk_norm"]:
        s["blocks/sub0/attn/q_norm"] = ((L, hd), "float32", 0.0)
        s["blocks/sub0/attn/k_norm"] = ((L, hd), "float32", 0.0)
    return s


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    tree: Dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


@functools.lru_cache(maxsize=None)
def _maker(spec_items: Tuple):
    def make(key):
        flat = {}
        for i, (path, (shape, dtype, std)) in enumerate(spec_items):
            k = jax.random.fold_in(key, i)
            if std == 0.0:
                flat[path] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                flat[path] = (jax.random.normal(k, shape, jnp.dtype(dtype))
                              * jnp.asarray(std, jnp.dtype(dtype)))
        return _nest(flat)
    return jax.jit(make)


def make(dims: Dict, seed: int) -> Dict:
    """Every weight of the model, on the default device, in one call."""
    items = tuple(sorted(leaf_specs(dims).items()))
    return _maker(items)(seed_key(seed))
