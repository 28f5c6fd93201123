"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds the mesh.

Every mesh here has Auto axes: the sharding rules place arrays through
``with_sharding_constraint``/``NamedSharding``, which ``jax.make_mesh``'s
default Explicit axes refuse.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.config import MeshConfig


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(cfg: MeshConfig):
    return _auto_mesh(cfg.shape, cfg.axis_names)


def make_local_mesh(model: Optional[int] = None):
    """Mesh over whatever devices exist (tests / smoke runs).

    Raises ``ValueError`` (not an assert — those vanish under ``python -O``)
    when the model axis does not divide, or exceeds, the device count.
    """
    n = len(jax.devices())
    model = model or 1
    if model > n:
        raise ValueError(
            f"model axis {model} exceeds the {n} available device(s); "
            f"start with XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"or lower --tp")
    if n % model != 0:
        raise ValueError(
            f"model axis {model} does not divide the {n} available device(s)")
    return _auto_mesh((n // model, model), ("data", "model"))
