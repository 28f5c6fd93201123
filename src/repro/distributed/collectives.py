"""Distributed-optimization collectives.

* :func:`int8_allreduce_mean` — gradient-compression all-reduce: per-tensor
  max-abs scale (psum-max), int8 quantise, int32 psum, dequantise.  Runs as a
  ``shard_map`` over the data axes so the quantised payload is what crosses
  the interconnect (visible as integer collectives in the lowered HLO).
* :func:`int8_roundtrip` — the pjit-friendly variant: quantise→dequantise
  around GSPMD's implicit all-reduce.  Numerically equivalent error model
  when per-replica batches are i.i.d.; used by the trainer when the step is
  GSPMD-partitioned end-to-end (explicit shard_map over the data axes would
  forbid GSPMD's model-axis partitioning of the same tensors).
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any


def _quantise(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_roundtrip(tree: Pytree) -> Pytree:
    """Quantise-dequantise each leaf (the QSGD error model under pjit)."""

    def f(g):
        g32 = g.astype(jnp.float32)
        q, scale = _quantise(g32)
        return (q.astype(jnp.float32) * scale).astype(g.dtype)

    return jax.tree.map(f, tree)


def int8_allreduce_mean(
    tree: Pytree, mesh: Mesh, data_axes: Sequence[str] = ("data",)
) -> Pytree:
    """Mean-all-reduce `tree` over `data_axes` with an int8 payload.

    Leaves must be replicated over the mesh's other axes (the usual layout of
    per-replica gradients in pure data parallelism).
    """
    axes = tuple(a for a in data_axes if a in mesh.axis_names)
    if not axes:
        return tree
    n = 1
    for a in axes:
        n *= mesh.shape[a]

    def reduce_leaf(g):
        def body(gl):
            gl32 = gl.astype(jnp.float32)
            # shared scale across replicas so the int32 sum is exact
            local_max = jnp.max(jnp.abs(gl32))
            scale = jax.lax.pmax(local_max, axes) / 127.0 + 1e-12
            q = jnp.clip(jnp.round(gl32 / scale), -127, 127).astype(jnp.int8)
            s = jax.lax.psum(q.astype(jnp.int32), axes)
            return (s.astype(jnp.float32) * scale / n).astype(gl.dtype)

        return jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=P(*[None] * g.ndim),
            out_specs=P(*[None] * g.ndim),
        )(g)

    return jax.tree.map(reduce_leaf, tree)
