"""Runs ``bench/run.py``'s ``main`` in this process at the rehearsal sizes,
so that a test can break the timed path underneath it first."""

import json

import jax
import pytest

from bench import run

_KEYS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes", "jax_compilation_cache_max_size")


@pytest.fixture
def rehearse(tmp_path, monkeypatch, capsys):
    """``rehearse(cell, *flags)`` -> the run's result line, as a dict."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    saved = {k: getattr(jax.config, k) for k in _KEYS}

    def go(cell, *flags, seed=2 ** 32 + 99):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                       "--trace", "0", "--rehearse", *flags])
        out = capsys.readouterr().out
        assert rc == 0
        return json.loads(out.strip().splitlines()[-1])

    yield go
    for k, v in saved.items():
        jax.config.update(k, v)
