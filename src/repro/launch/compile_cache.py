"""Persistent XLA compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR`` names the directory when it is set; otherwise
the cache lives at a fixed directory inside the checkout (``.jax_cache``,
git-ignored).  A later run must find what an earlier one wrote, so the path is
never derived from a temp name, a process id or the time.  Entry points (``chip_smoke.py``,
``repro.launch.serve``, the benchmark mains) call :func:`setup_compile_cache`
once at start-up; importing this module changes nothing.

JAX does not persist executables that carry host callbacks, so the cache
holds the prefill graphs and kernels but never the fused decode graphs.
"""

from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
