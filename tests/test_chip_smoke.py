"""The entry-point plumbing the chip runs depend on: where the persistent
compile cache lives, and that ``chip_smoke.py`` refuses to pass without a
chip or without the repo around it."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env", ["", "/var/cache/neo-xla"])
def test_compile_cache_dir(env, monkeypatch, restore_cache_dir):
    """The environment names the cache directory; unset, it is the fixed
    ``.jax_cache`` at the repo root (the same path in every process)."""
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = env or os.path.join(REPO, ".jax_cache")
    assert compile_cache.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def _run_smoke(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _has_result_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_chip_smoke_fails_without_a_chip():
    proc = _run_smoke(REPO)
    assert proc.returncode != 0
    assert not _has_result_line(proc.stdout)
    assert "'cpu'" in proc.stderr  # names the platform it found


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert not _has_result_line(proc.stdout)
