"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Models annotate tensors with *logical* axis names ("batch", "heads", "d_ff",
"vocab", "experts", "kv_blocks", ...).  A :class:`ShardingContext` — active
inside a ``with activate(ctx):`` block — resolves logical names to mesh axes
and applies ``with_sharding_constraint``.  With no active context (CPU smoke
tests, single device) every annotation is a no-op, so the same model code runs
everywhere.

Rules of thumb encoded here (see DESIGN.md §5):
  * ``batch`` always shards over ("pod", "data") — serving replicas / DP.
  * Megatron TP over "model" for heads / d_ff / vocab / experts.
  * decode-KV layout is per-arch: kv-heads sharded when they divide the model
    axis, otherwise KV *pages* shard over "model" and decode attention runs
    split-K via shard_map (``kv_shard_mode="blocks"``).
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ArchConfig

Axes = Union[None, str, Tuple[str, ...]]


def default_rules(cfg: ArchConfig, mesh: Mesh) -> Dict[str, Axes]:
    axis_names = mesh.axis_names
    has_pod = "pod" in axis_names
    batch_axes: Axes = ("pod", "data") if has_pod else ("data",)
    model_ax = mesh.shape.get("model", 1)

    rules: Dict[str, Axes] = {
        "batch": batch_axes,
        "seq": None,
        "d_model": None,
        "heads": "model",
        "head_dim": None,
        "d_ff": "model",
        "vocab": "model",
        "experts": "model",
        "expert_cap": None,
        "state": None,
        "layers": None,
        "kv_heads": None,
        "kv_seq": None,
        "kv_blocks": None,
        "conv": None,
        "frames": None,
    }
    # Decode-KV layout policy.
    if cfg.kv_shard_mode == "heads" and cfg.num_kv_heads % model_ax == 0:
        rules["kv_heads"] = "model"
    elif cfg.kv_shard_mode == "blocks":
        rules["kv_seq"] = "model"
        rules["kv_blocks"] = "model"
    # Head sharding only pays off when heads divide the axis; GSPMD pads
    # otherwise, which we accept for the >axis cases (40H on 16) but avoid for
    # tiny models where padding dominates (14H on 16 → replicate).
    if cfg.num_heads < model_ax:
        rules["heads"] = None
    # RWKV/Mamba recurrent heads shard over model when they divide evenly.
    if cfg.ssm is not None and cfg.num_heads % model_ax == 0:
        rules["heads"] = "model"
    return rules


@dataclass
class ShardingContext:
    mesh: Mesh
    rules: Dict[str, Axes]
    cfg: Optional[ArchConfig] = None

    @classmethod
    def for_arch(cls, cfg: ArchConfig, mesh: Mesh, overrides: Optional[Dict[str, Axes]] = None) -> "ShardingContext":
        rules = default_rules(cfg, mesh)
        rules.update(dict(cfg.sharding_overrides))
        if overrides:
            rules.update(overrides)
        return cls(mesh=mesh, rules=rules, cfg=cfg)

    def spec(self, logical: Sequence[Optional[str]]) -> P:
        parts = []
        used: set = set()
        for name in logical:
            ax = self.rules.get(name) if name else None
            if ax is None:
                parts.append(None)
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            axes = tuple(a for a in axes if a in self.mesh.axis_names and a not in used)
            used.update(axes)
            if not axes:
                parts.append(None)
            elif len(axes) == 1 and isinstance(ax, str):
                parts.append(axes[0])
            else:
                # Rules declared as tuples (e.g. batch over ("pod", "data"))
                # stay tuples even when filtering leaves a single axis — the
                # spec semantics are identical and callers can rely on the
                # declared form.
                parts.append(axes)
        return P(*parts)

    def sharding(self, logical: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical))


_state = threading.local()


def current_context() -> Optional[ShardingContext]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def activate(ctx: Optional[ShardingContext]):
    prev = current_context()
    _state.ctx = ctx
    try:
        if ctx is not None:
            with ctx.mesh:
                yield ctx
        else:
            yield None
    finally:
        _state.ctx = prev


def shard(x, *logical: Optional[str]):
    """Annotate `x` with logical axes; no-op without an active context."""
    if tp_axis() is not None:
        # Inside a shard_map TP body every array is already the local shard;
        # global sharding constraints are meaningless (and rejected) there.
        return x
    ctx = current_context()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.sharding(logical))


def logical_spec(*logical: Optional[str]) -> P:
    ctx = current_context()
    if ctx is None:
        return P()
    return ctx.spec(logical)


def sharding_for(*logical: Optional[str]) -> Optional[NamedSharding]:
    ctx = current_context()
    if ctx is None:
        return None
    return ctx.sharding(logical)


def model_axis_size() -> int:
    ctx = current_context()
    if ctx is None:
        return 1
    return ctx.mesh.shape.get("model", 1)


def mesh_axis_names() -> Tuple[str, ...]:
    ctx = current_context()
    if ctx is None:
        return ()
    return tuple(ctx.mesh.axis_names)


# ---------------------------------------------------------------------------
# Gather-TP (reduction-free tensor parallelism)
# ---------------------------------------------------------------------------
# The serving engine's TP scheme shards the COLUMN dimension of QKV and the
# MLP up-projections across the "model" axis, keeps the O/down projections
# (and embeddings/norms) replicated, and concatenates the per-shard partial
# activations with a tiled all_gather before each replicated projection.
# Every cross-shard combine is a pure concatenation — no all-reduce — so the
# float summation order inside every einsum is identical to the single-device
# graph and greedy decode stays BITWISE identical at any TP degree.
#
# Model code marks the gather points with :func:`tp_allgather`, which is an
# identity outside a TP body — the TP=1 graphs are untouched.  Executor code
# wraps its shard_map bodies in ``with tp_body("model"):`` so the model's
# ``shard(...)`` annotations (global-view constraints) turn into no-ops while
# tracing the per-shard program.

def tp_axis() -> Optional[str]:
    """Mesh axis of the enclosing shard_map TP body, or None outside one."""
    return getattr(_state, "tp_axis", None)


@contextlib.contextmanager
def tp_body(axis: str = "model"):
    """Mark the dynamic extent in which a per-shard TP program is traced."""
    prev = tp_axis()
    _state.tp_axis = axis
    try:
        yield
    finally:
        _state.tp_axis = prev


def tp_allgather(x, axis: int):
    """Concatenate per-shard partials along ``axis`` (tiled all_gather).

    Identity when not tracing inside :func:`tp_body` — single-device model
    code is byte-for-byte unchanged.  ``tiled=True`` makes this a pure
    concat of the shards in axis-index order, the reduction-free combine
    that keeps gather-TP bitwise identical to the unsharded graph.
    """
    ax = tp_axis()
    if ax is None:
        return x
    return jax.lax.all_gather(x, ax, axis=axis % x.ndim, tiled=True)


def gather_tp_spec(logical: Sequence[Optional[str]], axis: str = "model") -> P:
    """PartitionSpec for one parameter leaf under gather-TP.

    Column-shard the MLP up-projections (trailing logical axis "d_ff") and
    the QKV projections (trailing ("heads"|"kv_heads", head_dim) pair);
    replicate everything else — O/down projections, embeddings, norms.
    Works on the stacked per-layer leaves too (their logical tuples carry a
    leading ``None`` for the layer axis).
    """
    t = tuple(logical)
    if t and t[-1] == "d_ff":
        return P(*((None,) * (len(t) - 1)), axis)
    if len(t) >= 2 and t[-2] in ("heads", "kv_heads") and t[-1] is None:
        return P(*((None,) * (len(t) - 2)), axis, None)
    return P()
